"""Compiles for a described TPU v5e chip, with no chip attached.

The TPU compiler refuses what interpret mode accepts: block shapes that
break Mosaic's tiling rules, and programs that do not fit the chip's
16 GB.  These tests compile the two Pallas kernels at the widths the main
path uses and the streamed train step at the ``dtdg_epinions`` snapshot
shape, so such a regression fails here instead of on the chip.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and only the worker that runs this
file needs it.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.core import models as mdl
from repro.kernels.mproduct.mproduct import banded_ttm
from repro.kernels.segment_spmm import ops as spmm_ops
from repro.kernels.segment_spmm.segment_spmm import bucketed_segment_sum
from repro.optim import adamw
from repro.stream import train_loop

HBM_BYTES = 16 * 10**9                  # one v5e chip
# tmgcn's full config: the dtdg_epinions shape, N=755,200
CFG = registry.get_arch("tmgcn").make_config()
N = CFG.num_nodes
# edge lanes of one padded epinions snapshot: the smoothed-edge max of
# the 32-step seeded trace (2,098,287) plus N self-loop lanes, rounded
# to 128
E_LANES = 2_853_504
SPMM_NODES = 65_536                     # segment_spmm pads F to 128 lanes


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    assert total < HBM_BYTES, m
    return total


def test_banded_ttm_compiles_at_tmgcn_width(one_chip):
    """One checkpoint block of tmgcn's M-product: T=8, NF=N*hidden."""
    x = _sds((8, N * CFG.hidden), jnp.float32, one_chip)
    compiled = jax.jit(lambda v: banded_ttm(v, CFG.window, 0,
                                            interpret=False)
                       ).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("epb", [128, 4096])
def test_bucketed_segment_sum_compiles(one_chip, epb):
    """The kernel alone: node_block 128, feat_block 128."""
    nb = SPMM_NODES // 128
    compiled = jax.jit(lambda d, m: bucketed_segment_sum(
        d, m, node_block=128, feat_block=128, interpret=False)).lower(
        _sds((nb, epb), jnp.int32, one_chip),
        _sds((nb, epb, 128), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_segment_spmm_compiles_with_bucketing(one_chip):
    """The wrapper users call: jnp bucketing + gather + the kernel, at the
    model's width F=6 and the trace's ~2.78 edges per vertex."""
    e = int(SPMM_NODES * 2.78)
    compiled = jax.jit(lambda x, ed, w: spmm_ops.segment_spmm(
        x, ed, w, SPMM_NODES, edges_per_block=4096, interpret=False)).lower(
        _sds((SPMM_NODES, 6), jnp.float32, one_chip),
        _sds((e, 2), jnp.int32, one_chip),
        _sds((e,), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_stream_train_step_fits_one_chip(one_chip):
    """The per-snapshot streamed step (the main path) at the epinions
    snapshot shape fits one chip's HBM."""
    opt = adamw.AdamWConfig(lr=1e-2, warmup_steps=10, total_steps=64,
                            weight_decay=0.0)
    params = jax.eval_shape(
        lambda: mdl.init_params(jax.random.PRNGKey(0), CFG))
    opt_state = jax.eval_shape(adamw.init_state, params)
    carries = jax.eval_shape(lambda p: mdl.init_carries(CFG, p), params)

    def place(tree):
        return jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    step = train_loop.make_stream_train_step(CFG, opt)
    compiled = step.lower(
        place(params), place(opt_state), place(carries),
        _sds((N, CFG.feat_in), jnp.float32, one_chip),
        _sds((E_LANES, 2), jnp.int32, one_chip),
        _sds((E_LANES,), jnp.float32, one_chip),
        _sds((E_LANES,), jnp.float32, one_chip),
        _sds((N,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip)).compile()
    assert _fits(compiled) < 4 * 10**9
