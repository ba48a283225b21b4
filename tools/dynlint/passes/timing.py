"""Timing-discipline pass.

Invariant (PR 10): performance timing inside ``src/`` goes through
``repro.obs`` — ``obs.span`` / ``obs.stopwatch`` for measured regions,
``obs.now_s`` for point timestamps — so every measurement lands on one
clock, shows up in exported traces, and disappears when the tracer is
off.  Raw monotonic-clock reads (``time.perf_counter[_ns]`` /
``time.monotonic[_ns]``) scattered through the code produce numbers no
trace can see.

Flagged: any call to those four functions in ``src/`` files, whether
via the module (``time.perf_counter()``, including ``import time as
t``) or a from-import (``from time import perf_counter as pc``).
Exempt by construction: ``repro/obs/`` (the clock's one home) and
``repro/ft/`` (the StepTimer context-manager is the sanctioned raw
consumer, and ft must import nothing heavy).  ``time.time()`` is NOT
flagged — wall-clock provenance stamps are legitimate.

Deliberate exceptions carry ``# dynlint: allow[timing]``.
"""

from __future__ import annotations

import ast
from pathlib import PurePath

from tools.dynlint import astutil as au
from tools.dynlint.core import Finding, Source

PASS_ID = "timing"

_CLOCK_FNS = ("perf_counter", "perf_counter_ns",
              "monotonic", "monotonic_ns")
_EXEMPT_PARTS = ("obs", "ft", "tests", "examples")


def _in_scope(path: str) -> bool:
    parts = PurePath(path).parts
    if "src" not in parts:
        return False
    return not any(p in parts for p in _EXEMPT_PARTS)


def _clock_names(tree: ast.AST) -> tuple[set[str], set[str]]:
    """(module aliases of ``time``, local names bound to clock fns)."""
    mods: set[str] = set()
    fns: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == "time":
                    mods.add(alias.asname or "time")
        elif isinstance(node, ast.ImportFrom) and node.module == "time":
            for alias in node.names:
                if alias.name in _CLOCK_FNS:
                    fns.add(alias.asname or alias.name)
    return mods, fns


def check(src: Source) -> list[Finding]:
    if not _in_scope(src.path):
        return []
    mods, fns = _clock_names(src.tree)
    if not mods and not fns:
        return []
    out: list[Finding] = []
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.Call):
            continue
        full = au.call_name(node)
        if full is None:
            continue
        hit = None
        if "." in full:
            mod, tail = full.rsplit(".", 1)
            if mod in mods and tail in _CLOCK_FNS:
                hit = tail
        elif full in fns:
            hit = full
        if hit is not None:
            out.append(Finding(
                PASS_ID, src.path, node.lineno,
                f"raw {hit}() read — route timing through repro.obs "
                "(obs.stopwatch for measured regions, obs.span for "
                "traced phases, obs.now_s for point timestamps) so it "
                "lands on the tracer clock; deliberate raw reads carry "
                "`# dynlint: allow[timing]`"))
    return out
