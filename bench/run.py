#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Set-up
(trace generation, the program's host set-up, one warm-up epoch that
compiles or loads from ``<checkout>/.jax_cache``) is followed by whole
training epochs back to back for ``--seconds``, then by the comparison
with the plain reference.  The last line of standard output is the
result object; the numbers compared, each beside its limit, are the last
lines of standard error.  With ``--trace 1`` the window runs under the
profiler and the result carries the per-layer metrics instead of the
end-to-end ones.  It refuses to run where JAX finds no TPU, or fewer
chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
PEAKS = Path(__file__).resolve().parent / "peaks.json"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(path.read_text())
    if device_kind not in table["kinds"]:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"{path.name}; known: {sorted(table['kinds'])}")
    return table["kinds"][device_kind]


def accelerator(chips: int):
    """The devices JAX finds, if they are TPUs and at least ``chips``."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX's default backend is "
                         f"{devices[0].platform!r}; the benchmark runs "
                         "only on the chip")
    if len(devices) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX finds "
                         f"{len(devices)}")
    return devices


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory, removed after reading)")
    args = ap.parse_args(argv)

    # the persistent compile cache lives at a fixed path in the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(ROOT / "src"))
    import cell as cell_mod

    # a cell this tree cannot run fails here, before the chip is touched
    cell = cell_mod.load_cell(args.workload)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from repro.launch.compile_cache import enable_compile_cache

    devices = accelerator(cell.chips)
    peaks = load_peaks(devices[0].device_kind)
    log(f"jax {jax.__version__}, {len(devices)} x {devices[0].device_kind}"
        f", compile cache {enable_compile_cache()}")
    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
    try:
        out = cell_mod.run(cell, args.seed, args.seconds, bool(args.trace),
                           t_start=T_START, peaks=peaks, devices=devices,
                           trace_dir=trace_dir, log=log)
    finally:
        if args.trace_dir is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    for name, v in out["check"].items():
        log(f"check {name} {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
