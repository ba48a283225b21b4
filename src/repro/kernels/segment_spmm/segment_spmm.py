"""Pallas TPU kernel: bucketed segment-sum via one-hot MXU matmuls.

The GCN aggregate ``A_tilde @ X`` is a gather (read x[src]) followed by a
scatter-add (accumulate into dst).  On GPU the paper uses cuSPARSE SpMM; the
TPU has no scatter unit, and XLA lowers segment-sum to a serialized
scatter-add loop.  The TPU-native adaptation: turn the scatter into a
*one-hot matrix product* so it runs on the MXU systolic array.

Data layout (produced by ``ops.bucket_edges`` on host / in jnp):
  * edges sorted by destination and bucketed by destination block:
    ``dst_local``: (NB, EPB) int32 — dst index *within* its node block;
    padded lanes carry ``block_size`` (a dump row sliced off after).
  * ``messages``: (NB, EPB, F) — x[src] * w, gathered OUTSIDE the kernel
    (XLA's dynamic-gather is already TPU-efficient; the scatter is not).

Grid: (NB, F / F_BLK); each step computes

    out[i, :, fb] = OneHot(dst_local[i])^T @ messages[i, :, fb]

an (N_BLK x EPB) @ (EPB x F_BLK) MXU matmul.  VMEM working set:
EPB*F_BLK + EPB*N_BLK + N_BLK*F_BLK floats — all tile-aligned (128 lanes).

``dst_local`` enters the kernel as (NB, 1, EPB) with a (1, 1, EPB) block:
Mosaic requires a block's last two dims to be (8, 128)-divisible or equal
to the array's, and a (1, EPB) block of the 2-D array is neither.  The
one-hot is built directly in its transposed (N_BLK, EPB) form, with the
destination row broadcast along sublanes, so the contraction is a plain
matmul.  The dot runs at HIGHEST precision: one-hot weights are exact,
and the sums must stay f32-exact like the segment-sum oracle's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import resolve_interpret  # noqa: F401 (re-export)

DEFAULT_NODE_BLOCK = 128
DEFAULT_FEAT_BLOCK = 128


def _kernel(dst_ref, msg_ref, out_ref, *, node_block: int):
    # dst_ref: (1, 1, EPB) int32; msg_ref: (1, EPB, FB); out_ref: (1, NB, FB)
    dst = dst_ref[0]                                   # (1, EPB)
    msgs = msg_ref[0]                                  # (EPB, FB)
    # Transposed one-hot over the node block; padded lanes (dst ==
    # node_block or any value >= node_block) match no row and vanish.
    rows = jax.lax.broadcasted_iota(jnp.int32, (node_block, dst.shape[1]), 0)
    onehot_t = (rows == dst).astype(msgs.dtype)        # (NB, EPB)
    acc = jnp.dot(onehot_t, msgs, precision=jax.lax.Precision.HIGHEST,
                  preferred_element_type=jnp.float32)
    out_ref[0] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("node_block", "feat_block",
                                             "interpret"))
def bucketed_segment_sum(dst_local: jax.Array, messages: jax.Array,
                         node_block: int = DEFAULT_NODE_BLOCK,
                         feat_block: int = DEFAULT_FEAT_BLOCK,
                         interpret: bool | None = None) -> jax.Array:
    """(NB, EPB) int32 x (NB, EPB, F) -> (NB, node_block, F)."""
    interpret = resolve_interpret(interpret)
    nb, epb = dst_local.shape
    f = messages.shape[-1]
    if f % feat_block != 0:
        raise ValueError(f"F={f} must be a multiple of feat_block={feat_block}")
    grid = (nb, f // feat_block)
    return pl.pallas_call(
        functools.partial(_kernel, node_block=node_block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, epb), lambda i, _j: (i, 0, 0)),
            pl.BlockSpec((1, epb, feat_block), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, node_block, feat_block),
                               lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((nb, node_block, f), messages.dtype),
        interpret=interpret,
    )(dst_local.reshape(nb, 1, epb), messages)
