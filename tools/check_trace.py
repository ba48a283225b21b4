#!/usr/bin/env python
"""Trace checker: schema-validate an exported ``--trace`` file and
assert the spans a healthy run must contain.

CI's trace-smoke step runs a short streamed_mesh fit with ``--trace``
and then gates on this script: the trace must be a valid Chrome-trace /
Perfetto file (``repro.obs.validate_trace``), every streamed ``round``
span must come with the round's ``round.transfer``, ``round.step`` and
``round.sync`` spans (reconstruction, step dispatch, and the loss read
that completes the round), and any ``--require``'d span names (e.g. the
prefetch staging threads) must be present.

Usage::

    python tools/check_trace.py trace.json \
        --require prefetch.stage --require prefetch.wait

Exits non-zero with one line per problem.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.obs import load_trace, validate_trace  # noqa: E402

# the spans every round of a traced streamed_mesh fit records (the
# sampled schedule's ``round`` spans, marked schedule=sampled, have none)
ROUND_SPANS = ("round.transfer", "round.step", "round.sync")


def check(path: str, require: list[str]) -> list[str]:
    events, meta = load_trace(path)
    problems = [f"{path}: {p}" for p in validate_trace(events)]
    if problems:
        return problems
    names = {ev["name"] for ev in events}
    for name in require:
        if name not in names:
            problems.append(f"{path}: required span {name!r} missing "
                            f"(have {sorted(names)})")
    rounds = sorted({ev["args"]["round"] for ev in events
                     if ev["name"] == "round"
                     and "round" in ev.get("args", {})
                     and ev["args"].get("schedule") != "sampled"})
    for r in rounds:
        have = {ev["name"] for ev in events
                if ev.get("args", {}).get("round") == r}
        missing = [n for n in ROUND_SPANS if n not in have]
        # a cut-off tail (preemption / stop_fn, or ring overflow) may
        # lose the last round's spans; every other round must be whole
        if missing and (r != rounds[-1]
                        or meta.get("dropped_spans", 0) == 0):
            problems.append(f"{path}: round {r} missing spans {missing}")
    return problems


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace", help="trace file written by --trace")
    ap.add_argument("--require", action="append", default=[],
                    metavar="NAME", help="span name that must be present "
                    "(repeatable)")
    args = ap.parse_args()
    problems = check(args.trace, args.require)
    for p in problems:
        print(p, file=sys.stderr)
    if not problems:
        events, _ = load_trace(args.trace)
        rounds = {ev["args"]["round"] for ev in events
                  if ev["name"] == "round" and "round" in ev.get("args", {})}
        print(f"{args.trace}: OK ({len(events)} events, "
              f"{len(rounds)} rounds)")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
