"""Synthetic graph generators.

Two generators mirror the paper's experimental setup:

* ``random_dynamic_graph`` — the weak-scaling generator of §6.3: each snapshot
  is drawn independently with ``m = N * density`` random edges.
* ``evolving_dynamic_graph`` — real DTDG datasets evolve slowly (§3.2); this
  generator makes that controllable: snapshot t+1 keeps a (1 - churn) fraction
  of snapshot t's edges and resamples the rest, so the expected topology
  overlap between consecutive snapshots is exactly ``1 - churn``.  Used to
  evaluate the graph-difference transfer technique across overlap regimes.

Both return plain numpy edge lists (list of (E_t, 2) int32 arrays): the dynamic
graph lives on the *host* (that is the point of the paper's transfer
optimization) and is shipped block-by-block to the device.
"""

from __future__ import annotations

import numpy as np


def unique_edges(edges: np.ndarray, n: int) -> np.ndarray:
    """Sorted distinct rows of an (E, 2) edge list with ids in [0, n).

    Same result as ``np.unique(edges, axis=0)``: the packed key
    ``src * n + dst`` orders exactly like the (src, dst) rows, and a 1-D
    int64 unique is ~50x faster than the row-wise one at 10^6 edges.
    """
    key = np.unique(edges[:, 0].astype(np.int64) * n
                    + edges[:, 1].astype(np.int64))
    return np.stack([key // n, key % n], axis=1).astype(np.int32)


def _random_edges(rng: np.random.Generator, n: int, m: int) -> np.ndarray:
    src = rng.integers(0, n, size=m, dtype=np.int64)
    dst = rng.integers(0, n, size=m, dtype=np.int64)
    return unique_edges(np.stack([src, dst], axis=1), n)


def random_dynamic_graph(num_nodes: int, num_steps: int, density: float,
                         seed: int = 0) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    m = int(num_nodes * density)
    return [_random_edges(rng, num_nodes, m) for _ in range(num_steps)]


def evolving_dynamic_graph(num_nodes: int, num_steps: int, density: float,
                           churn: float = 0.1, seed: int = 0
                           ) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    m = int(num_nodes * density)
    snaps = [_random_edges(rng, num_nodes, m)]
    for _ in range(1, num_steps):
        prev = snaps[-1]
        keep = rng.random(prev.shape[0]) >= churn
        kept = prev[keep]
        fresh = _random_edges(rng, num_nodes, max(m - kept.shape[0], 0))
        snaps.append(unique_edges(np.concatenate([kept, fresh], axis=0),
                                  num_nodes))
    return snaps


def random_static_graph(num_nodes: int, num_edges: int,
                        seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return _random_edges(rng, num_nodes, num_edges)


def random_positions(num_nodes: int, box: float = 10.0,
                     seed: int = 0) -> np.ndarray:
    """Synthetic 3D coordinates for molecular archs on non-molecular shapes."""
    rng = np.random.default_rng(seed)
    return rng.uniform(0.0, box, size=(num_nodes, 3)).astype(np.float32)


def random_features(num_nodes: int, dim: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.normal(0, 1, size=(num_nodes, dim)).astype(np.float32)


def degree_features(edges: np.ndarray, num_nodes: int) -> np.ndarray:
    """(in-degree, out-degree) input features, as used by the paper (§6.1)."""
    return np.stack([np.bincount(edges[:, 1], minlength=num_nodes),
                     np.bincount(edges[:, 0], minlength=num_nodes)],
                    axis=1).astype(np.float32)
