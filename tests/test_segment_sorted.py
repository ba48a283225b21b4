"""The destination-sorted aggregation against the random-order one.

``segment.spmm`` sorts its lanes by the row they write before it reduces,
and its custom VJP sorts the transpose by source.  These tests compare its
values and both cotangents with a plain gather + ``jax.ops.segment_sum`` in
the edges' own order, differentiated by ``jax.grad``, on the layouts a
snapshot's edge buffer holds: zero-weight and padded ``(0, 0)`` lanes,
rows nothing writes, a hub row, duplicate edges, and no lanes at all.
The two sum in different orders, so they agree to f32 rounding, not bit
for bit.  The Pallas sort and reduction that the TPU runs are checked in
interpret mode.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graph import segment
from repro.kernels.segment_spmm.bitonic import bitonic_sort
from repro.kernels.segment_spmm.segment_spmm import sorted_segment_sum

RTOL, ATOL = 1e-5, 1e-6
N, E, F = 300, 2000, 6
HUB_EDGES = 10_000
CASES = ["random", "zero_weights", "padded_lanes", "empty_rows", "hub",
         "duplicates", "no_lanes"]


def _graph(case: str, seed: int = 0):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, N, E)
    dst = rng.integers(0, N, E)
    w = rng.uniform(0.1, 1.0, E)
    if case == "zero_weights":
        w[rng.random(E) < 0.3] = 0.0
    elif case == "padded_lanes":
        pad = rng.random(E) < 0.25
        src[pad], dst[pad], w[pad] = 0, 0, 0.0
    elif case == "empty_rows":
        # no lane writes or reads an even row
        src, dst = src | 1, dst | 1
    elif case == "hub":
        src = np.concatenate([src, rng.integers(0, N, HUB_EDGES)])
        dst = np.concatenate([dst, np.full(HUB_EDGES, 7)])
        w = np.concatenate([w, rng.uniform(0.1, 1.0, HUB_EDGES)])
    elif case == "duplicates":
        src, dst, w = (np.tile(a[:E // 4], 4) for a in (src, dst, w))
    elif case == "no_lanes":
        src, dst, w = src[:0], dst[:0], w[:0]
    edges = np.stack([src, dst], axis=1).astype(np.int32)
    x = rng.normal(size=(N, F)).astype(np.float32)
    return (jnp.asarray(x), jnp.asarray(edges),
            jnp.asarray(w.astype(np.float32)))


def _random_order(x, edges, w, num_nodes=N):
    msgs = jnp.take(x, edges[:, 0], axis=0) * w[:, None]
    return jax.ops.segment_sum(msgs, edges[:, 1], num_segments=num_nodes)


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=RTOL, atol=ATOL)


def _grads(fn, x, edges, w, cot):
    """Cotangents of ``x`` and the weights under the cotangent ``cot``."""
    return jax.grad(lambda a, b: jnp.sum(fn(a, edges, b, N) * cot),
                    argnums=(0, 1))(x, w)


@pytest.mark.parametrize("case", CASES)
def test_values_match_random_order(case):
    x, edges, w = _graph(case)
    _close(segment.spmm(x, edges, w, N), _random_order(x, edges, w))


@pytest.mark.parametrize("case", CASES)
def test_cotangents_match_autodiff(case):
    x, edges, w = _graph(case, seed=1)
    cot = jax.random.normal(jax.random.PRNGKey(1), (N, F))
    got = _grads(segment.spmm, x, edges, w, cot)
    want = _grads(_random_order, x, edges, w, cot)
    for g, r in zip(got, want, strict=True):
        _close(g, r)


@pytest.mark.parametrize("case", CASES)
def test_vmap_and_jit(case):
    """Two snapshots under ``vmap`` inside ``jit``: values and both
    cotangents, snapshot by snapshot."""
    graphs = [_graph(case, seed=s) for s in (2, 3)]
    x, edges, w = (jnp.stack(a) for a in zip(*graphs, strict=True))
    cot = jax.random.normal(jax.random.PRNGKey(2), (2, N, F))

    def batched(fn):
        def loss(a, b):
            out = jax.vmap(lambda xa, ea, wa: fn(xa, ea, wa, N))(a, edges, b)
            return jnp.sum(out * cot), out
        return jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))

    (gx, gw), out = batched(segment.spmm)(x, w)
    (rx, rw), ref = batched(_random_order)(x, w)
    for got, want in ((out, ref), (gx, rx), (gw, rw)):
        _close(got, want)


@pytest.mark.parametrize("case", CASES)
def test_pallas_reduction_matches_random_order(case):
    x, edges, w = _graph(case, seed=4)
    keys, msgs = segment.sorted_lanes(x, edges[:, 0], edges[:, 1], w, N)
    got = sorted_segment_sum(keys, msgs, N, interpret=True)
    _close(got, _random_order(x, edges, w))


@pytest.mark.parametrize("case", CASES)
def test_bitonic_sort_orders_lanes_like_xla_sort(case):
    """The TPU's lane sort, in interpret mode: the same keys in order, and
    each lane's payloads still beside its key.  The case's lanes repeat to
    16,384 or more, and 64-row blocks hold 8,192, so that the network's
    tile-pair stages and cross-block passes run too."""
    _, edges, w = _graph(case, seed=5)
    reps = -(-16_384 // max(w.shape[0], 1))
    key = jnp.tile(jnp.where(w != 0, edges[:, 1], N), reps)
    w = jnp.tile(w, reps)
    lane = jnp.arange(key.shape[0], dtype=jnp.int32)
    got = bitonic_sort(key, lane, w, block_rows=64, interpret=True)
    k, lanes, ws = (np.asarray(a) for a in got)
    np.testing.assert_array_equal(k, np.sort(np.asarray(key)))
    np.testing.assert_array_equal(np.sort(lanes), np.asarray(lane))
    np.testing.assert_array_equal(np.asarray(key)[lanes], k)
    np.testing.assert_array_equal(np.asarray(w)[lanes], ws)
