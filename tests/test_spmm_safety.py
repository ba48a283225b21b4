"""Regression tests for the segment_spmm safety fixes (PR 2 satellites).

No hypothesis dependency (unlike test_kernels.py) so these always run:
* interpret resolution — the "Pallas" path must never silently interpret on
  a real accelerator backend, and must interpret on CPU;
* bucketing overflow — tight edges_per_block budgets are detected (via
  checkify) and recoverable (dense fallback), never silently wrong.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import checkify

from repro.core import gcn as gcnlib
from repro.kernels.common import resolve_interpret
from repro.kernels.segment_spmm import ops as spmm_ops

N, E, F = 192, 800, 64


def _graph(seed=0, skewed=False):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, N, size=(E,))
    dst = np.zeros((E,), np.int64) if skewed else rng.integers(0, N, (E,))
    edges = np.stack([src, dst], axis=1).astype(np.int32)
    w = rng.normal(size=(E,)).astype(np.float32)
    x = rng.normal(size=(N, F)).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(edges), jnp.asarray(w)


# ------------------------------------------------- interpret resolution ----

def test_interpret_resolves_from_backend(monkeypatch):
    """None -> interpret on CPU, compiled kernel everywhere else."""
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert resolve_interpret(None) is True
    for backend in ("tpu", "gpu", "cuda"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert resolve_interpret(None) is False, backend


def test_segment_spmm_default_interpret_runs_on_cpu():
    """The default (interpret=None) path must work on the CPU backend and
    match the oracle — i.e. resolution actually reaches pallas_call."""
    assert jax.default_backend() == "cpu"
    x, edges, w = _graph()
    got = spmm_ops.segment_spmm(x, edges, w, N)
    want = spmm_ops.segment_spmm_ref(x, edges, w, N)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_spatial_aggregate_threads_interpret():
    """core.gcn.spatial_aggregate forwards the flag to the kernel wrapper."""
    x, edges, w = _graph(seed=1)
    got = gcnlib.spatial_aggregate(x, edges, w, N, use_pallas=True,
                                   interpret=True)
    want = gcnlib.spatial_aggregate(x, edges, w, N, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------- bucketing overflow ----

def test_overflow_count_zero_for_default_budget():
    x, edges, w = _graph(seed=2, skewed=True)
    cnt = spmm_ops.bucket_overflow_count(edges, w, N, jnp.int32(E))
    assert int(cnt) == 0


def test_overflow_count_ignores_zero_weight_padding():
    """Padded lanes (weight 0) beyond the budget are a lossless drop."""
    x, edges, w = _graph(seed=3, skewed=True)
    cnt_real = int(spmm_ops.bucket_overflow_count(edges, w, N,
                                                  jnp.int32(128)))
    cnt_pad = int(spmm_ops.bucket_overflow_count(edges, jnp.zeros_like(w),
                                                 N, jnp.int32(128)))
    assert cnt_real > 0
    assert cnt_pad == 0


def test_tight_budget_overflow_surfaces_via_checkify():
    """A skewed destination distribution with a stats-sized budget raises
    under checkify instead of silently dropping edges."""
    x, edges, w = _graph(seed=4, skewed=True)
    fn = checkify.checkify(
        lambda xx, ee, ww: spmm_ops.segment_spmm(xx, ee, ww, N,
                                                 edges_per_block=128),
        errors=checkify.user_checks)
    err, _ = fn(x, edges, w)
    with pytest.raises(checkify.JaxRuntimeError,
                       match="overflow edges_per_block"):
        err.throw()
    # the safe default budget passes the same check
    fn_ok = checkify.checkify(
        lambda xx, ee, ww: spmm_ops.segment_spmm(xx, ee, ww, N),
        errors=checkify.user_checks)
    err_ok, out = fn_ok(x, edges, w)
    err_ok.throw()
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(spmm_ops.segment_spmm_ref(x, edges, w,
                                                              N)),
        rtol=1e-4, atol=1e-4)


def test_checked_wrapper_falls_back_dense_on_overflow():
    x, edges, w = _graph(seed=5, skewed=True)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        got = spmm_ops.segment_spmm_checked(x, edges, w, N,
                                            edges_per_block=128)
    assert any("falling back" in str(r.message) for r in rec)
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(spmm_ops.segment_spmm_ref(x, edges, w, N)),
        rtol=1e-4, atol=1e-4)


def test_checked_wrapper_stays_on_kernel_when_budget_fits():
    x, edges, w = _graph(seed=6, skewed=False)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        got = spmm_ops.segment_spmm_checked(x, edges, w, N,
                                            edges_per_block=E)
    assert not any("falling back" in str(r.message) for r in rec)
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(spmm_ops.segment_spmm_ref(x, edges, w, N)),
        rtol=1e-4, atol=1e-4)
