"""int8 error-feedback compression tests (``repro.dist.compression``).

Pinned claims:

* quantize/dequantize error is bounded by half a quantization step
  (scale = absmax/127) per element, and the wire dtype is int8
  (randomized property sweeps live in ``test_compression_props.py``
  behind the hypothesis guard);
* absmax edge cases are safe: all-zero tensors quantize to zeros with a
  positive scale, denormal inputs stay finite, ±inf saturates to ±127
  without manufacturing NaN;
* ``compressed_psum`` satisfies the error-feedback identity through a real
  ``shard_map`` psum, converges unbiased under accumulation, and
  preserves pytree structure;
* ``make_quantized_a2a`` ships exactly what the residual ledger says it
  shipped (output == plain all-to-all of ``y + res - new_res``,
  bitwise), preserves the input dtype, and its custom backward tracks
  the plain all-to-all gradient to within quantization error.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.dist import compression

PARTS = 4


def _mesh():
    devs = jax.devices()
    if len(devs) < PARTS:
        pytest.skip(f"needs {PARTS} devices")
    return Mesh(np.array(devs[:PARTS]), ("data",))


def _reducer(mesh):
    return shard_map(
        lambda g, r: compression.compressed_psum(g, "data", r),
        mesh=mesh,
        in_specs=(P("data", None), P("data", None)),
        out_specs=(P("data", None), P("data", None)),
        check_vma=False)


# ---------------------------------------------------------- round-trip ------

def test_int8_roundtrip_error_bound():
    g = jax.random.normal(jax.random.PRNGKey(0), (257,), jnp.float32) * 3.0
    q, scale = compression._quantize(g)
    assert q.dtype == jnp.int8
    deq = q.astype(jnp.float32) * scale
    step = float(np.max(np.abs(np.asarray(g)))) / 127.0
    assert np.isclose(float(scale), step, rtol=1e-6)
    err = np.max(np.abs(np.asarray(deq) - np.asarray(g)))
    assert err <= 0.5 * step + 1e-7


def test_quantize_zero_gradient_is_safe():
    q, scale = compression._quantize(jnp.zeros((8,), jnp.float32))
    assert float(scale) > 0.0            # clamped off zero: no NaN divide
    assert np.all(np.asarray(q) == 0)


# ------------------------------------------------- quantizer corners -------

def test_quantize_absmax_edge_cases():
    # all-zero: positive clamped scale, zero payload (no 0/0 NaN)
    q, scale = compression.quantize(jnp.zeros((4,), jnp.float32))
    assert float(scale) > 0.0 and np.all(np.asarray(q) == 0)
    # denormal absmax: scale clamps to tiny, nothing overflows to inf/NaN
    tiny = np.float32(1e-42)             # subnormal in f32
    q, scale = compression.quantize(jnp.full((4,), tiny))
    deq = np.asarray(compression.dequantize(q, scale))
    assert np.all(np.isfinite(deq))
    # ±inf: inf/clamped-finite-scale clips cleanly to ±127, never NaN
    g = jnp.asarray([np.inf, -np.inf, 1.0, -1.0], jnp.float32)
    q, scale = compression.quantize(g)
    qn = np.asarray(q)
    assert qn[0] == 127 and qn[1] == -127
    assert not np.any(np.isnan(np.asarray(compression.dequantize(q, scale))))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ef_quantize_residual_dtype(dtype):
    """Residuals accumulate in f32 regardless of the payload dtype (a
    bf16 residual would round away exactly the error it must carry)."""
    g = jnp.linspace(-1.0, 1.0, 16).astype(dtype)
    deq, res = compression.ef_quantize(g, jnp.zeros((16,), jnp.float32))
    assert deq.dtype == jnp.float32
    assert res.dtype == jnp.float32


# ------------------------------------------------- error-feedback psum ------

def test_compressed_psum_error_feedback_identity():
    mesh = _mesh()
    g = np.asarray(jax.random.normal(jax.random.PRNGKey(1),
                                     (PARTS, 32), jnp.float32))
    res0 = np.zeros_like(g)
    red, res1 = _reducer(mesh)(jnp.asarray(g), jnp.asarray(res0))
    red, res1 = np.asarray(red), np.asarray(res1)
    # psum output is replicated: every shard row carries the same mean
    np.testing.assert_allclose(red, np.broadcast_to(red[0], red.shape),
                               atol=0)
    # exact identity: what was reduced is what left the residual ledger
    np.testing.assert_allclose(red[0], (g + res0 - res1).mean(axis=0),
                               atol=1e-5)


def test_compressed_psum_accumulation_converges_unbiased():
    mesh = _mesh()
    reduce_ = _reducer(mesh)
    g = np.asarray(jax.random.normal(jax.random.PRNGKey(2),
                                     (PARTS, 32), jnp.float32))
    true_mean = g.mean(axis=0)
    step = np.abs(g).max() / 127.0

    res = jnp.zeros_like(jnp.asarray(g))
    reds = []
    n_steps = 8
    for _ in range(n_steps):
        red, res = reduce_(jnp.asarray(g), res)
        reds.append(np.asarray(red)[0])
        # residual bounded by ~half a quantization step, forever
        assert np.max(np.abs(np.asarray(res))) <= step

    # sum_k red_k = K * true_mean - mean(res_K): averaging over steps
    # kills the truncation at rate 1/K — error feedback carries it all
    avg = np.mean(reds, axis=0)
    np.testing.assert_allclose(avg, true_mean, atol=step / n_steps + 1e-6)
    # and a single step is already within one quantization step
    np.testing.assert_allclose(reds[0], true_mean, atol=step + 1e-6)


def test_compressed_psum_preserves_tree_structure():
    mesh = _mesh()
    tree = {"w": jnp.ones((PARTS, 8), jnp.float32),
            "b": jnp.full((PARTS, 2), 2.0, jnp.float32)}
    res = compression.init_residual(tree)
    assert jax.tree.structure(res) == jax.tree.structure(tree)
    f = shard_map(
        lambda g, r: compression.compressed_psum(g, "data", r),
        mesh=mesh,
        in_specs=(P("data", None), P("data", None)),
        out_specs=(P("data", None), P("data", None)),
        check_vma=False)
    red, new_res = f(tree, res)
    assert jax.tree.structure(red) == jax.tree.structure(tree)
    # identical shards quantize exactly: mean == the common value
    np.testing.assert_allclose(np.asarray(red["w"]), 1.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(red["b"]), 2.0, atol=1e-5)


# ------------------------------------------------ quantized all-to-all ------

def _y_res(seed, dtype=jnp.float32):
    """Global (PARTS*2, PARTS*3, 4) activation + f32 residual."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    shape = (PARTS * 2, PARTS * 3, 4)
    y = (jax.random.normal(k1, shape, jnp.float32) * 2.0).astype(dtype)
    res = jax.random.normal(k2, shape, jnp.float32) * 0.01
    return y, res


def test_quantized_a2a_matches_residual_ledger_exactly():
    """The a2a output IS the plain all-to-all of what the ledger says was
    shipped (y + res - new_res), bitwise: scales travel with their
    pieces, so remote dequantization reproduces the local ``sent``."""
    mesh = _mesh()
    qa2a = compression.make_quantized_a2a("data", PARTS, 1, 0)

    def body(y, res):
        out, new_res = qa2a(y, res)
        ref = jax.lax.all_to_all(
            y.astype(jnp.float32) + res - new_res, "data",
            split_axis=1, concat_axis=0, tiled=True)
        return out, new_res, ref

    y, res = _y_res(3)
    out, new_res, ref = shard_map(
        body, mesh=mesh,
        in_specs=(P("data"), P("data")),
        out_specs=(P("data"), P("data"), P("data")),
        check_vma=False)(y, res)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # the residual is bounded by half a per-piece quantization step
    step = float(jnp.max(jnp.abs(y.astype(jnp.float32) + res))) / 127.0
    assert float(jnp.max(jnp.abs(new_res))) <= 0.5 * step * (1 + 1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantized_a2a_preserves_payload_dtype(dtype):
    mesh = _mesh()
    qa2a = compression.make_quantized_a2a("data", PARTS, 1, 0)
    y, res = _y_res(5, dtype)
    out, new_res = shard_map(
        qa2a, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P("data"), P("data")), check_vma=False)(y, res)
    assert out.dtype == dtype
    assert new_res.dtype == jnp.float32


def test_quantized_a2a_gradient_tracks_plain_a2a():
    """The custom backward (transposed quantized a2a) agrees with the
    plain all-to-all gradient to within one quantization step."""
    mesh = _mesh()
    qa2a = compression.make_quantized_a2a("data", PARTS, 1, 0)

    def loss_q(y, res):
        out, _ = qa2a(y, res)
        return jax.lax.psum(jnp.sum(jnp.sin(out)), "data")

    def loss_ref(y, res):
        out = jax.lax.all_to_all(y, "data", split_axis=1, concat_axis=0,
                                 tiled=True)
        return jax.lax.psum(jnp.sum(jnp.sin(out)), "data")

    y, res = _y_res(7)
    res = jnp.zeros_like(res)
    grads = {}
    for name, fn in (("q", loss_q), ("ref", loss_ref)):
        g = shard_map(
            jax.grad(fn), mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=P("data"), check_vma=False)(y, res)
        grads[name] = np.asarray(g)
    # two quantization perturbations stack: the forward error moves
    # cos(out) by ~one activation step and the backward quantizes the
    # cotangent itself — both a small multiple of step ~ absmax/127
    # (|cos| <= 1 here, so absolute tolerances are honest)
    diff = np.abs(grads["q"] - grads["ref"])
    assert diff.max() <= 0.2
    assert diff.mean() <= 0.05
