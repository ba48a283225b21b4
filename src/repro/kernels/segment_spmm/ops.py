"""Jit'd public wrapper for the segment SpMM Pallas kernel.

``segment_spmm(x, edges, w, n)`` == ``ref.segment_spmm_ref`` and is a drop-in
for ``repro.graph.segment.spmm``.  The bucketing (sort by dst + pad each node
block's edge list to a common budget) happens in jnp so it stays inside the
jitted step function; datasets with static topology can pre-bucket once on
host via ``bucket_edges_host``.

Safety properties of the bucketed layout:

* ``interpret`` defaults to ``None`` and resolves from the active backend
  (interpret only on CPU) — real TPU/GPU backends always get the compiled
  kernel, never the silent interpreter emulation.
* A caller-supplied ``edges_per_block`` that is too small for a skewed
  destination distribution would silently drop overflow edges; the wrapper
  now counts weighted overflow lanes and surfaces the count through
  ``checkify.debug_check`` (wrap the jitted caller in
  ``checkify.checkify(..., errors=checkify.user_checks)`` to materialize the
  error).  ``segment_spmm_checked`` is the documented dense-fallback path:
  it prechecks the bucket layout on host and reroutes overflowing calls to
  the XLA segment-sum oracle instead of returning a wrong answer.
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import checkify

from repro.kernels.segment_spmm import ref as _ref
from repro.kernels.segment_spmm.segment_spmm import (
    DEFAULT_FEAT_BLOCK, DEFAULT_NODE_BLOCK, bucketed_segment_sum,
    resolve_interpret)


def _pad_feat(x: jax.Array, feat_block: int) -> jax.Array:
    f = x.shape[-1]
    pad = (-f) % feat_block
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x


@functools.partial(jax.jit, static_argnames=("num_nodes", "node_block"))
def bucket_overflow_count(edges: jax.Array, edge_weights: jax.Array,
                          num_nodes: int, edges_per_block: jax.Array,
                          node_block: int = DEFAULT_NODE_BLOCK) -> jax.Array:
    """Weighted edges that a (node_block, edges_per_block) layout would drop.

    Zero-weight lanes (the padding convention) never count: dropping them is
    lossless.  Returns an int32 scalar; jit-compatible, usable as a host-side
    precheck (``segment_spmm_checked``) or a device-side debug check.
    """
    bucket = edges[:, 1] // node_block
    nb = -(-num_nodes // node_block)
    counts = jax.ops.segment_sum(jnp.ones_like(bucket), bucket,
                                 num_segments=nb)
    order = jnp.argsort(bucket, stable=True)
    bucket_sorted = jnp.take(bucket, order)
    w_sorted = jnp.take(edge_weights, order)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(edges.shape[0]) - jnp.take(starts, bucket_sorted)
    dropped = (rank >= edges_per_block) & (w_sorted != 0)
    return jnp.sum(dropped.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=(
    "num_nodes", "node_block", "feat_block", "edges_per_block", "interpret"))
def segment_spmm(x: jax.Array, edges: jax.Array, edge_weights: jax.Array,
                 num_nodes: int, node_block: int = DEFAULT_NODE_BLOCK,
                 feat_block: int = DEFAULT_FEAT_BLOCK,
                 edges_per_block: int | None = None,
                 interpret: bool | None = None) -> jax.Array:
    """A_tilde @ x with the Pallas kernel (interpret resolved per backend).

    edges: (E, 2); padded lanes must carry weight 0 (they are routed to a
    dump bucket anyway).  Worst-case edges_per_block defaults to E (safe for
    skewed graphs); pass dataset statistics for tight buckets — overflow is
    then detected (never silent): the weighted-overflow count feeds a
    ``checkify.debug_check``, and ``segment_spmm_checked`` documents the
    dense-fallback route.
    """
    interpret = resolve_interpret(interpret)
    e = edges.shape[0]
    f = x.shape[-1]
    nb = -(-num_nodes // node_block)
    epb = edges_per_block or min(e, _round_up(e, 128))
    epb = _round_up(epb, 128)

    # Sort edges by destination block and compute positions within buckets.
    dst = edges[:, 1]
    bucket = dst // node_block
    order = jnp.argsort(bucket, stable=True)
    dst_sorted = jnp.take(dst, order)
    src_sorted = jnp.take(edges[:, 0], order)
    w_sorted = jnp.take(edge_weights, order)
    bucket_sorted = jnp.take(bucket, order)

    # Rank of each edge within its bucket (positions for the padded layout).
    ones = jnp.ones_like(bucket_sorted)
    counts = jax.ops.segment_sum(ones, bucket_sorted, num_segments=nb)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(e) - jnp.take(starts, bucket_sorted)
    valid = rank < epb
    # Overflow = weighted edges beyond the bucket budget.  Detected, not
    # silent: callers wrapping in checkify get the error; everyone else can
    # precheck via bucket_overflow_count / segment_spmm_checked.
    overflow = jnp.sum((~valid & (w_sorted != 0)).astype(jnp.int32))
    checkify.debug_check(
        overflow == 0,
        "segment_spmm: {n} weighted edges overflow edges_per_block="
        f"{epb} (node_block={node_block}); results would drop their "
        "contributions — raise edges_per_block or use "
        "segment_spmm_checked for the dense fallback", n=overflow)

    # Scatter into the (NB, EPB) bucketed layout.
    flat_pos = jnp.where(valid, bucket_sorted * epb + rank, nb * epb)
    dst_local = jnp.full((nb * epb + 1,), node_block, dtype=jnp.int32)
    dst_local = dst_local.at[flat_pos].set(
        (dst_sorted - bucket_sorted * node_block).astype(jnp.int32),
        mode="drop")[:-1].reshape(nb, epb)
    src_b = jnp.zeros((nb * epb + 1,), dtype=jnp.int32)
    src_b = src_b.at[flat_pos].set(src_sorted.astype(jnp.int32),
                                   mode="drop")[:-1].reshape(nb, epb)
    w_b = jnp.zeros((nb * epb + 1,), dtype=edge_weights.dtype)
    w_b = w_b.at[flat_pos].set(w_sorted, mode="drop")[:-1].reshape(nb, epb)

    # Gather + weight OUTSIDE the kernel (XLA handles gathers well on TPU).
    msgs = jnp.take(_pad_feat(x, feat_block), src_b.reshape(-1), axis=0)
    msgs = msgs.reshape(nb, epb, -1) * w_b[..., None].astype(x.dtype)

    out = bucketed_segment_sum(dst_local, msgs, node_block=node_block,
                               feat_block=feat_block, interpret=interpret)
    return out.reshape(nb * node_block, -1)[:num_nodes, :f]


def segment_spmm_checked(x: jax.Array, edges: jax.Array,
                         edge_weights: jax.Array, num_nodes: int,
                         node_block: int = DEFAULT_NODE_BLOCK,
                         feat_block: int = DEFAULT_FEAT_BLOCK,
                         edges_per_block: int | None = None,
                         interpret: bool | None = None) -> jax.Array:
    """Dense-fallback path for tight ``edges_per_block`` budgets.

    Prechecks the bucket layout (one jitted reduction, synced to host); if
    the requested budget would drop weighted edges, warns and reroutes to
    the XLA segment-sum oracle — correct for any degree skew — instead of
    returning a silently wrong aggregate.  Use this wrapper when
    edges_per_block comes from dataset statistics that a live stream might
    exceed; the default (worst-case) budget never overflows.
    """
    if edges_per_block is not None:
        # mirror the kernel wrapper's lane rounding so the precheck sees the
        # same budget the bucketing will actually use
        epb = _round_up(edges_per_block, 128)
        n_over = int(bucket_overflow_count(edges, edge_weights, num_nodes,
                                           jnp.int32(epb),
                                           node_block=node_block))
        if n_over:
            warnings.warn(
                f"segment_spmm: edges_per_block={edges_per_block} drops "
                f"{n_over} weighted edges; falling back to the dense "
                "segment-sum path", stacklevel=2)
            return _ref.segment_spmm_ref(x, edges, edge_weights, num_nodes)
    return segment_spmm(x, edges, edge_weights, num_nodes,
                        node_block=node_block, feat_block=feat_block,
                        edges_per_block=edges_per_block,
                        interpret=interpret)


def _round_up(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m


def bucket_edges_host(edges: np.ndarray, edge_weights: np.ndarray,
                      num_nodes: int, node_block: int = DEFAULT_NODE_BLOCK):
    """Host-side one-time bucketing for static topologies.

    Returns (dst_local (NB, EPB), src (NB, EPB), w (NB, EPB)) with EPB sized
    to the dataset's max per-block degree sum (rounded to 128).
    """
    nb = -(-num_nodes // node_block)
    bucket = edges[:, 1] // node_block
    counts = np.bincount(bucket, minlength=nb)
    epb = max(int(_round_up(int(counts.max() or 1), 128)), 128)
    dst_local = np.full((nb, epb), node_block, dtype=np.int32)
    src = np.zeros((nb, epb), dtype=np.int32)
    w = np.zeros((nb, epb), dtype=np.float32)
    fill = np.zeros((nb,), dtype=np.int64)
    for i in range(edges.shape[0]):
        b = bucket[i]
        k = fill[b]
        dst_local[b, k] = edges[i, 1] - b * node_block
        src[b, k] = edges[i, 0]
        w[b, k] = edge_weights[i]
        fill[b] += 1
    return dst_local, src, w


# Re-exported oracle for tests/benchmarks.
segment_spmm_ref = _ref.segment_spmm_ref
