"""GCN spatial module (Kipf-Welling, Eq. 2) over padded snapshots.

The sparse-dense aggregate ``A_tilde @ X`` is the compute hot spot; it is
served by ``repro.graph.segment.spmm``, which sorts its lanes by destination
and reduces them with the Pallas TPU kernel (``repro.kernels.segment_spmm``)
on a TPU.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.graph import segment
from repro.obs import stages

Array = jax.Array


def init_gcn_params(key: Array, f_in: int, f_out: int,
                    dtype=jnp.float32) -> dict:
    scale = 1.0 / jnp.sqrt(f_in)
    return {
        "w": (jax.random.uniform(key, (f_in, f_out), dtype=jnp.float32,
                                 minval=-scale, maxval=scale)).astype(dtype),
        "b": jnp.zeros((f_out,), dtype=dtype),
    }


@jax.named_scope(stages.SPMM)
def spatial_aggregate(x: Array, edges: Array, edge_weights: Array,
                      num_nodes: int) -> Array:
    """``A_tilde @ X`` for one snapshot. x: (N, F) -> (N, F)."""
    return segment.spmm(x, edges, edge_weights, num_nodes)


def gcn_apply(params: dict, x: Array, edges: Array, edge_weights: Array,
              num_nodes: int, *, activation: Callable = jax.nn.relu,
              concat_skip: bool = False, pre_aggregated: bool = False) -> Array:
    """One GCN op on one snapshot.

    concat_skip implements CD-GCN's skip connection (§5.1):
        Y0 = A_tilde X;  Y1 = Y0 W;  Y = act(concat(Y0, Y1))  (F + F' wide)
    pre_aggregated: x already equals A_tilde @ X (the paper's first-layer
    pre-computation, §5.5) — skip the sparse product.
    """
    y0 = x if pre_aggregated else spatial_aggregate(
        x, edges, edge_weights, num_nodes)
    y1 = y0 @ params["w"] + params["b"]
    if concat_skip:
        return activation(jnp.concatenate([y0, y1], axis=-1))
    return activation(y1)
