"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode).

Covers shapes x dtypes for all three Pallas kernels + hypothesis property
tests on the destination-sorted segment-sum layout.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (see requirements.txt)")
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.graph import segment
from repro.kernels.flash_decode import ops as fd_ops
from repro.kernels.mproduct import ops as mp_ops
from repro.kernels.segment_spmm.ref import segment_spmm_ref
from repro.kernels.segment_spmm.segment_spmm import sorted_segment_sum


# ------------------------------------------------------- segment_spmm ------

def _kernel_spmm(x, edges, w, n):
    """``A_tilde @ x`` as a TPU runs it, the kernel interpreted: the lanes
    of ``segment.spmm`` sorted by destination, then the Pallas reduction."""
    keys, msgs = segment.sorted_lanes(x, edges[:, 0], edges[:, 1], w, n)
    return sorted_segment_sum(keys, msgs, n, interpret=True)


@pytest.mark.parametrize("n,e,f", [(200, 1000, 64), (300, 2000, 100),
                                   (128, 500, 128), (64, 64, 32),
                                   (1000, 4000, 256)])
@pytest.mark.parametrize("dtype", [np.float32])
def test_segment_spmm_matches_oracle(n, e, f, dtype):
    rng = np.random.default_rng(n + e)
    edges = rng.integers(0, n, size=(e, 2)).astype(np.int32)
    w = rng.normal(size=(e,)).astype(dtype)
    x = rng.normal(size=(n, f)).astype(dtype)
    got = _kernel_spmm(jnp.asarray(x), jnp.asarray(edges),
                       jnp.asarray(w), n)
    want = segment_spmm_ref(jnp.asarray(x), jnp.asarray(edges),
                            jnp.asarray(w), n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_segment_spmm_masked_edges_ignored():
    n, e, f = 50, 200, 64
    rng = np.random.default_rng(0)
    edges = rng.integers(0, n, size=(e, 2)).astype(np.int32)
    w = rng.normal(size=(e,)).astype(np.float32)
    w[e // 2:] = 0.0   # padded lanes carry zero weight
    x = rng.normal(size=(n, f)).astype(np.float32)
    got = _kernel_spmm(jnp.asarray(x), jnp.asarray(edges),
                       jnp.asarray(w), n)
    want = segment_spmm_ref(
        jnp.asarray(x[:, :f]), jnp.asarray(edges[:e // 2]),
        jnp.asarray(w[:e // 2]), n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(8, 300), e=st.integers(1, 800),
       f=st.sampled_from([16, 64, 100]), seed=st.integers(0, 2**31))
def test_segment_spmm_property(n, e, f, seed):
    rng = np.random.default_rng(seed)
    edges = rng.integers(0, n, size=(e, 2)).astype(np.int32)
    w = rng.normal(size=(e,)).astype(np.float32)
    x = rng.normal(size=(n, f)).astype(np.float32)
    got = _kernel_spmm(jnp.asarray(x), jnp.asarray(edges),
                       jnp.asarray(w), n)
    want = segment_spmm_ref(jnp.asarray(x), jnp.asarray(edges),
                            jnp.asarray(w), n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ----------------------------------------------------------- mproduct ------

@pytest.mark.parametrize("t,n,f,w", [(16, 8, 4, 3), (32, 16, 6, 5),
                                     (8, 4, 2, 1), (24, 10, 6, 7),
                                     (64, 32, 8, 9)])
def test_mproduct_matches_dense_ttm(t, n, f, w):
    rng = np.random.default_rng(t * w)
    x = jnp.asarray(rng.normal(size=(t, n, f)).astype(np.float32))
    got = mp_ops.m_product(x, w)
    want = mp_ops.banded_ttm_ref(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(t=st.integers(2, 40), n=st.integers(1, 12), f=st.integers(1, 8),
       w=st.integers(1, 12), seed=st.integers(0, 2**31))
def test_mproduct_property(t, n, f, w, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(t, n, f)).astype(np.float32))
    got = mp_ops.m_product(x, w)
    want = mp_ops.banded_ttm_ref(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_mproduct_sliced_with_prefix_equals_full():
    from repro.core import temporal
    rng = np.random.default_rng(3)
    t, n, f, w = 12, 6, 4, 4
    x = jnp.asarray(rng.normal(size=(t, n, f)).astype(np.float32))
    full = temporal.m_product(x, w)
    s = 6
    prefix = x[s - (w - 1):s]
    for use_pallas in (False, True):
        sl = temporal.m_product_with_prefix(x[s:], prefix, w, s,
                                            use_pallas=use_pallas)
        np.testing.assert_allclose(np.asarray(sl), np.asarray(full[s:]),
                                   rtol=1e-5, atol=1e-5)


# -------------------------------------------------------- flash_decode -----

@pytest.mark.parametrize("b,hq,kvh,d,s,blk", [
    (2, 8, 2, 64, 1024, 256), (1, 4, 4, 128, 512, 128),
    (4, 16, 4, 64, 2048, 512), (2, 8, 8, 64, 256, 128)])
def test_flash_decode_matches_oracle(b, hq, kvh, d, s, blk):
    rng = np.random.default_rng(b * s)
    q = jnp.asarray(rng.normal(size=(b, hq, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, kvh, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, kvh, d)).astype(np.float32))
    clen = jnp.asarray(rng.integers(1, s, size=(b,)).astype(np.int32))
    got = fd_ops.decode_attention(q, k, v, clen, kv_block=blk)
    want = fd_ops.flash_decode_ref(q, k, v, clen)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_flash_decode_bf16():
    rng = np.random.default_rng(9)
    b, hq, kvh, d, s = 2, 4, 2, 64, 512
    q = jnp.asarray(rng.normal(size=(b, hq, d)), dtype=jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(b, s, kvh, d)), dtype=jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(b, s, kvh, d)), dtype=jnp.bfloat16)
    clen = jnp.asarray([100, 500], dtype=jnp.int32)
    got = fd_ops.decode_attention(q, k, v, clen, kv_block=128)
    want = fd_ops.flash_decode_ref(q, k, v, clen)
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(want, dtype=np.float32),
                               rtol=2e-2, atol=2e-2)
