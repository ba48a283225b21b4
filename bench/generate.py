"""The benchmark's trace generator: seeded raw snapshots of an evolving
graph, read from a traffic file's parameters.

Adapted from the program's ``graph.generate.evolving_dynamic_graph``
(snapshot t+1 keeps 1 - churn of snapshot t's edges and draws the rest
afresh), with every count made exact so that no size depends on the
seed:

* every snapshot holds exactly ``m`` distinct edges;
* every step drops exactly ``d = round(churn * m)`` of them, chosen from
  the seed, and adds ``d`` edges that the trace has never held before.

So each smoothed union of ``w`` consecutive snapshots holds exactly
``m + (w - 1) * d`` edges and each delta drops and adds exactly ``d``:
every seed gives the same array shapes, the same compiled programs and
the same amount of work, in another arrangement.
"""

from __future__ import annotations

import numpy as np

GENERATORS = ("evolving",)
DEGREES = ("uniform",)


def raw_edges_per_snapshot(smoothed_edges: int, churn: float,
                           smoothing: str, window: int) -> int:
    """Raw edges per snapshot whose smoothed union holds ``smoothed_edges``.

    M-transform smoothing unions ``window`` consecutive snapshots: the
    oldest one whole, plus ``churn`` of it for each later step.
    """
    union = 1.0 if smoothing == "none" else 1.0 + (window - 1) * churn
    return round(smoothed_edges / union)


def _fresh(rng: np.random.Generator, num_nodes: int, k: int,
           seen: np.ndarray) -> np.ndarray:
    """``k`` distinct edge keys ``src * n + dst``, uniform over the
    vertex pairs, none of them in the sorted array ``seen``."""
    out = np.empty(0, dtype=np.int64)
    while out.size < k:
        need = k - out.size
        cand = rng.integers(0, num_nodes * num_nodes,
                            size=need + need // 64 + 16, dtype=np.int64)
        _, first = np.unique(cand, return_index=True)
        cand = cand[np.sort(first)]             # distinct, in draw order
        pos = np.searchsorted(seen, cand)
        hit = seen[np.minimum(pos, seen.size - 1)] == cand if seen.size \
            else np.zeros(cand.size, dtype=bool)
        cand = cand[~hit]
        if out.size:
            cand = cand[~np.isin(cand, out)]
        out = np.concatenate([out, cand[:need]])
    return out


def evolving_trace(num_nodes: int, num_steps: int, edges_per_snapshot: int,
                   churn: float, seed: int) -> list[np.ndarray]:
    """Raw snapshots: a list of ``num_steps`` (m, 2) int32 edge lists
    ``(src, dst)``, each sorted by ``src * num_nodes + dst``."""
    rng = np.random.default_rng(seed)
    m = edges_per_snapshot
    d = round(churn * m)
    keys = np.sort(_fresh(rng, num_nodes, m, np.empty(0, dtype=np.int64)))
    seen = keys
    snaps = []
    for t in range(num_steps):
        if t:
            drop = rng.choice(m, size=d, replace=False)
            kept = np.delete(keys, drop)
            new = _fresh(rng, num_nodes, d, seen)
            keys = np.sort(np.concatenate([kept, new]))
            seen = np.sort(np.concatenate([seen, new]))
        snaps.append(np.stack([keys // num_nodes, keys % num_nodes],
                              axis=1).astype(np.int32))
    return snaps


def trace_for(config: dict, traffic: dict, seed: int) -> list[np.ndarray]:
    """Raw snapshots for a configuration under a traffic mix."""
    if traffic["generator"] not in GENERATORS:
        raise ValueError(f"unknown generator {traffic['generator']!r}")
    if traffic["degrees"] not in DEGREES:
        raise ValueError(f"unknown degree distribution "
                         f"{traffic['degrees']!r}")
    m = raw_edges_per_snapshot(config["smoothed_edges_per_snapshot"],
                               traffic["churn"], config["smoothing"],
                               config["window"])
    return evolving_trace(config["num_nodes"], config["num_steps"], m,
                          traffic["churn"], seed)
