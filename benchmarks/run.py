"""Benchmark driver — one section per paper table/figure.

Emits ``name,us_per_call,derived`` CSV rows:
  * graphdiff_bench      — Fig. 4 (graph-difference transfer + encoder
                           throughput + sharded streaming)
  * scaling_bench        — Fig. 5 strong scaling + Fig. 7 weak scaling
                           (+ the elastic ``rescale`` smoke row: re-shard
                           payload bytes + time-to-recompose; + the
                           out-of-core ``sampled`` smoke row: full-graph
                           budget refusals vs a sampled run that fits)
  * partition_compare    — Table 2 (snapshot vs hypergraph vertex part.)
  * checkpoint_bench     — §3.1/§6.2 (memory/time vs nb)
  * kernel_bench         — hot-spot op microbenchmarks
  * overlap_bench        — §6.5 compute/comm + stream transfer overlap
  * serve_bench          — online serving: warm vs cold query latency
                           (p50/p95 at batch 1/8/64) + live-ingest
                           events/s
  * obs_bench            — repro.obs tracer overhead: asserts the
                           disabled tracer costs <2% on a hot loop, and
                           reports the enabled-tracer cost for scale

``--smoke`` runs tiny shapes (the CI smoke job); ``--only a,b`` restricts
to named sections; ``--json-dir DIR`` additionally writes one
``BENCH_<section>.json`` artifact per section (suite name, repo SHA,
wall time, the CSV rows) for CI upload.
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import common
from benchmarks.common import header


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI (seconds, not minutes)")
    ap.add_argument("--only", default="",
                    help="comma-separated section names to run")
    ap.add_argument("--json-dir", default="",
                    help="write one BENCH_<section>.json artifact per "
                         "section into this directory")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    header()
    from benchmarks import (checkpoint_bench, graphdiff_bench, kernel_bench,
                            obs_bench, overlap_bench, partition_compare,
                            scaling_bench, serve_bench)
    smoke = args.smoke
    sections = [
        ("graphdiff", lambda: graphdiff_bench.run(
            **({"n": 256, "t": 12} if smoke else {}))),
        ("scaling", scaling_bench.run),
        ("rescale", lambda: scaling_bench.rescale_smoke(
            **({"n": 32, "t": 8} if smoke else {}))),
        ("compressed", lambda: scaling_bench.compressed_round(
            **({"n": 64, "t": 16} if smoke else {}))),
        ("sampled", lambda: scaling_bench.sampled_smoke(
            **({"n": 192, "t": 8} if smoke else {}))),
        ("partition_compare", partition_compare.run),
        ("checkpoint", lambda: checkpoint_bench.run(
            **({"n": 128, "t": 16} if smoke else {}))),
        ("kernels", kernel_bench.run),
        ("overlap", lambda: overlap_bench.run(smoke=smoke)),
        ("serve", lambda: serve_bench.run(
            **({"n": 96, "windows": 12, "events": 1200,
                "batches": (1, 8), "iters": 4} if smoke else {}))),
        ("obs", lambda: obs_bench.run(
            **({"units": 200, "reps": 3} if smoke else {}))),
    ]
    only = {s.strip() for s in args.only.split(",") if s.strip()}
    if only:
        unknown = only - {name for name, _ in sections}
        if unknown:
            raise SystemExit(f"unknown sections: {sorted(unknown)}")
        sections = [(n, f) for n, f in sections if n in only]
    failures = 0
    for name, fn in sections:
        print(f"# --- {name} ---", flush=True)
        first_row = len(common.ROWS)
        t0 = time.perf_counter()
        failed = False
        try:
            fn()
        except Exception:  # noqa: BLE001
            failures += 1
            failed = True
            print(f"# SECTION FAILED: {name}", flush=True)
            traceback.print_exc()
        if args.json_dir:
            common.write_bench_json(args.json_dir, name,
                                    common.ROWS[first_row:],
                                    time.perf_counter() - t0, failed)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
