"""Pallas TPU kernel: segment sum over lanes sorted by destination row.

The GCN aggregate ``A_tilde @ X`` is a gather (read x[src]) followed by a
reduction into the destination rows.  XLA lowers that reduction on a TPU
to a scatter-add whose cost grows with every element it updates, in
whatever order the lanes come.  Once the lanes are sorted by destination
(``repro.graph.segment`` sorts them), each block of output rows owns one
contiguous run of lanes, and the reduction becomes a short series of
one-hot products on the MXU.

Layout:
  * ``keys``: (E,) int32, ascending; a lane's destination row.  Lanes
    with a key outside ``[0, num_nodes)`` are left out.
  * ``msgs``: (F, E) float32, feature-major: one lane per column, so a
    chunk of lanes is a lane-dense (F, C) tile, F sublanes high.
  * E is a multiple of ``CHUNK``: the caller pads the lanes before it
    sorts them, so that no copy of the sorted arrays is made here.

Grid: one step per (block of ``ROWS`` output rows, chunk of ``CHUNK``
lanes) pair that overlaps.  A block's lanes ``[bounds[b], bounds[b + 1])``
come from ``searchsorted`` on the keys; the chunks that hold them are
visited in consecutive steps, so the block's (F, rows) output tile stays
in VMEM while each step adds

    out[:, block] += msgs[:, chunk] @ OneHot(keys[chunk] - r0)^T

an (F, C) x (C, rows) product on the MXU.  Lanes of a chunk that belong to
another block match no row of this one.  The schedule (the block and
chunk of every step) reaches the kernel as scalar prefetch and drives the
BlockSpecs, so Pallas pipelines the chunks' copies.  A block with no
lanes still takes one step, which writes its zeros.  The step count is
bounded by blocks + chunks whatever the degrees: no lane budget, no degree
bound, and a hub row only makes its block take more steps.  The dot runs
at HIGHEST precision: one-hot weights are exact, and the sums must stay
f32-exact like ``jax.ops.segment_sum``'s.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret  # noqa: F401 (re-export)

ROWS = 256        # output rows per block (lanes of the output tile)
CHUNK = 512       # lanes per step


def _kernel(block_ref, chunk_ref, steps_ref, keys_ref, msgs_ref, out_ref, *,
            rows: int):
    # keys_ref: (1, C) int32; msgs_ref: (F, C); out_ref: (F, rows)
    i = pl.program_id(0)
    b = block_ref[i]

    @pl.when((i == 0) | (block_ref[jnp.maximum(i - 1, 0)] != b))
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    @pl.when(i < steps_ref[0])
    def _():
        row_ids = jax.lax.broadcasted_iota(
            jnp.int32, (rows, keys_ref.shape[1]), 0) + b * rows
        onehot = (row_ids == keys_ref[...]).astype(jnp.float32)
        out_ref[...] += jax.lax.dot_general(
            msgs_ref[...], onehot, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)


def _schedule(keys: jax.Array, num_nodes: int, rows: int, chunk: int):
    """(block, chunk) of every grid step, and the number of live steps.

    Steps past the live ones repeat the last live step's indices, so they
    copy nothing and, skipped by the kernel, add nothing."""
    nb = -(-num_nodes // rows)
    nc = keys.shape[0] // chunk
    # lane offsets of each block; keys >= num_nodes fall past the last one
    bounds = jnp.searchsorted(
        keys, jnp.minimum(jnp.arange(nb + 1) * rows, num_nodes)
    ).astype(jnp.int32)
    first = jnp.minimum(bounds[:-1] // chunk, nc - 1)
    last = jnp.maximum(jnp.minimum(-(-bounds[1:] // chunk), nc), first + 1)
    ends = jnp.cumsum(last - first)
    step = jnp.arange(nb + nc, dtype=jnp.int32)
    block = jnp.minimum(jnp.searchsorted(ends, step, side="right"), nb - 1)
    at = first[block] + step - (ends[block] - (last - first)[block])
    return (block.astype(jnp.int32),
            jnp.minimum(at, last[block] - 1).astype(jnp.int32), ends[-1:])


@functools.partial(jax.jit, static_argnames=("num_nodes", "interpret"))
def sorted_segment_sum(keys: jax.Array, msgs: jax.Array, num_nodes: int,
                       interpret: bool = False) -> jax.Array:
    """(E,) ascending int32 keys x (F, E) f32 messages -> (num_nodes, F).

    Equals ``jax.ops.segment_sum(msgs.T, keys, num_nodes)`` up to the
    order of the f32 additions.  E must be a multiple of ``CHUNK``.
    """
    f, e = msgs.shape
    if e % CHUNK:
        raise ValueError(f"{e} lanes are not a multiple of CHUNK={CHUNK}")
    nb = -(-num_nodes // ROWS)
    block, at, steps = _schedule(keys, num_nodes, ROWS, CHUNK)
    out = pl.pallas_call(
        functools.partial(_kernel, rows=ROWS),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(block.shape[0],),
            in_specs=[pl.BlockSpec((1, CHUNK), lambda i, b, c, n: (0, c[i])),
                      pl.BlockSpec((f, CHUNK), lambda i, b, c, n: (0, c[i]))],
            out_specs=pl.BlockSpec((f, ROWS), lambda i, b, c, n: (0, b[i]))),
        out_shape=jax.ShapeDtypeStruct((f, nb * ROWS), jnp.float32),
        interpret=interpret,
    )(block, at, steps, keys[None], msgs)
    return out[:, :num_nodes].T
