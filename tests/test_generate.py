"""The synthetic trace generators and smoothing against their original
formulas.

``unique_edges`` (packed int64 keys), the ``np.bincount`` degree features
and the one-sort smoothing merge replaced ``np.unique(axis=0)``,
``np.add.at`` and ``np.unique(return_inverse=True)`` + ``np.minimum.at``;
the traces they build must stay bit-identical, or every seeded trace in
the repo changes.
"""

import numpy as np
import pytest

from repro.core import smoothing
from repro.graph import generate


def _evolving_rowwise(num_nodes, num_steps, density, churn, seed):
    """The generator as first written: row-wise ``np.unique(axis=0)``."""
    rng = np.random.default_rng(seed)
    m = int(num_nodes * density)

    def rand(k):
        src = rng.integers(0, num_nodes, size=k, dtype=np.int64)
        dst = rng.integers(0, num_nodes, size=k, dtype=np.int64)
        return np.unique(np.stack([src, dst], axis=1), axis=0).astype(np.int32)

    snaps = [rand(m)]
    for _ in range(1, num_steps):
        prev = snaps[-1]
        kept = prev[rng.random(prev.shape[0]) >= churn]
        fresh = rand(max(m - kept.shape[0], 0))
        snaps.append(np.unique(np.concatenate([kept, fresh], axis=0),
                               axis=0).astype(np.int32))
    return snaps


def _degree_add_at(edges, num_nodes):
    f = np.zeros((num_nodes, 2), dtype=np.float32)
    np.add.at(f[:, 0], edges[:, 1], 1.0)
    np.add.at(f[:, 1], edges[:, 0], 1.0)
    return f


@pytest.mark.parametrize("n,t,density,churn,seed", [
    (97, 6, 3.0, 0.1, 0),
    (500, 5, 2.0, 0.3, 1),
    (64, 4, 40.0, 0.5, 2),      # dense: many duplicate draws to dedup
])
def test_evolving_graph_matches_rowwise_unique(n, t, density, churn, seed):
    got = generate.evolving_dynamic_graph(n, t, density, churn, seed)
    want = _evolving_rowwise(n, t, density, churn, seed)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == np.int32
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("n,e,seed", [(50, 400, 0), (1000, 30, 1),
                                      (10, 0, 2)])
def test_degree_features_match_add_at(n, e, seed):
    rng = np.random.default_rng(seed)
    edges = rng.integers(0, n, size=(e, 2)).astype(np.int32)
    got = generate.degree_features(edges, n)
    want = _degree_add_at(edges, n)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got, want)


def test_unique_edges_matches_rowwise_unique():
    rng = np.random.default_rng(3)
    n = 37
    edges = rng.integers(0, n, size=(2000, 2))
    np.testing.assert_array_equal(
        generate.unique_edges(edges, n),
        np.unique(edges, axis=0).astype(np.int32))


def _merge_unique(edge_sets, weights):
    """The smoothing merge as first written."""
    all_edges = np.concatenate(edge_sets, axis=0)
    all_w = np.concatenate([np.full((e.shape[0],), w, dtype=np.float32)
                            for e, w in zip(edge_sets, weights)])
    key = all_edges[:, 0].astype(np.int64) * (all_edges.max() + 1) \
        + all_edges[:, 1].astype(np.int64)
    uniq, inv = np.unique(key, return_inverse=True)
    w = np.zeros(uniq.shape[0], dtype=np.float32)
    np.add.at(w, inv, all_w)
    first = np.full(uniq.shape[0], np.iinfo(np.int64).max, dtype=np.int64)
    np.minimum.at(first, inv, np.arange(all_edges.shape[0]))
    return all_edges[first].astype(np.int32), w


@pytest.mark.parametrize("mode", ["mproduct", "edgelife"])
def test_smoothing_matches_unique_merge(mode, monkeypatch):
    snaps = generate.evolving_dynamic_graph(300, 9, 3.0, 0.3, seed=4)
    if mode == "mproduct":
        got = smoothing.m_transform_sparse(snaps, 4)
        monkeypatch.setattr(smoothing, "_merge", _merge_unique)
        want = smoothing.m_transform_sparse(snaps, 4)
    else:
        got = smoothing.edge_life(snaps, 3)
        monkeypatch.setattr(smoothing, "_merge", _merge_unique)
        want = smoothing.edge_life(snaps, 3)
    for (ge, gv), (we, wv) in zip(zip(*got), zip(*want)):
        np.testing.assert_array_equal(ge, we)
        assert gv.dtype == wv.dtype == np.float32
        np.testing.assert_array_equal(gv, wv)
