#!/usr/bin/env python3
"""Readings that set the limits of a cell's correctness check.

    python3 bench/control.py --workload <name> --seeds 1 2 3 ... [--program]

For every seed, in one process and at the cell's own sizes:

* ``program`` (with ``--program``): one epoch of the program against the
  reference, as a run of the cell compares them, with no measured
  window — the lower readings;
* ``program_vs_highest`` (with ``--program``): the same against the
  reference at ``highest`` matmul precision: how far the configuration's
  precision lies from the exact float32 product (for the record; no
  limit uses it);
* ``control``: the reference computed in bfloat16 put in the program's
  place — it has to read well above the program;
* ``half_batch``: the reference with half of the vertices left out of
  the loss (the mean taken over the rest);
* ``no_exchange`` (cells on several chips): the reference with each
  chip's temporal stage fed only the snapshots it holds.

A step that returns its state unchanged reads 1 on ``grad_gap`` and
``change_gap`` by construction and needs no run.  Prints one JSON line
per seed and reading.  Runs only where JAX finds a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(cell, seed: int, program: bool) -> list[dict]:
    """Every reading of one seed, as dicts {seed, kind, numbers}."""
    import jax.numpy as jnp

    import cell as cell_mod
    import check
    import generate

    raw = generate.trace_for(cell.config, cell.traffic, seed)
    out = []
    prog = None
    if program:
        eng = cell_mod.build_program(cell, raw, seed)
        rr = eng.resolve()
        eng.fit()                                   # compiles the step
        key = cell_mod.STEP_CACHE_KEY[cell.config["plan"]["mode"]]
        rec = cell_mod.StepRecorder(rr.cache[key], cell_mod.kept_steps(cell))
        rr.cache[key] = rec
        res = eng.fit()
        prog = ([res.losses], *cell_mod.program_readings(rec, cell))
        del eng, rr, rec, res
    slices = cell_mod.reference_slices(cell, raw)
    ref = cell_mod.reference_readings(cell, slices, seed)
    if prog is not None:
        out.append(("program", check.compare(*prog, ref)))
        exact = cell_mod.reference_readings(cell, slices, seed,
                                            precision="highest")
        out.append(("program_vs_highest", check.compare(*prog, exact)))
    variants = [("control", {"dtype": jnp.bfloat16}),
                ("half_batch", {"fault": "half_batch"})]
    if cell.config["plan"]["shards"] > 1:
        variants.append(("no_exchange", {"fault": "no_exchange"}))
    for kind, kw in variants:
        r = cell_mod.reference_readings(cell, slices, seed, **kw)
        out.append((kind, check.compare([r.losses], r.first_grad, r.change,
                                        ref)))
    return [{"seed": seed, "kind": k, **v} for k, v in out]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true",
                    help="also read the program on each seed")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import cell as cell_mod
    import run

    cell = cell_mod.load_cell(args.workload)
    # the reference runs on one chip; the program needs the cell's
    run.accelerator(cell.chips if args.program else 1)
    for seed in args.seeds:
        t0 = time.perf_counter()
        for row in readings(cell, seed, args.program):
            row["seconds"] = time.perf_counter() - t0
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
