"""Segment-reduction message-passing primitives.

JAX has no CSR/CSC sparse (BCOO only), so all graph aggregation in this
framework is expressed as edge-index gather -> segment reduction.  The
``scatter_*`` reductions below lower to dynamic-gather + scatter-add HLO and
are shared by every GNN architecture in ``repro.models.gnn``.

``spmm`` is the SpMM layer of the paper (the GCN convolution ``A_tilde @ X``).
A TPU scatter-add costs per element it updates, in whatever order the lanes
come, so ``spmm`` first sorts its lanes by the row they write and reduces
contiguous runs.  On a TPU both steps are Pallas kernels of
``repro.kernels.segment_spmm``: a bitonic sort, whose code is a fraction of
XLA's sort's (the chip keeps a program's code in HBM), and the one-hot
reduction.  Elsewhere they are XLA's sort and a sorted ``segment_sum``.  Its
custom VJP sorts the transpose by source the same way.

Conventions
-----------
* ``edges``: int32 array of shape (E, 2) with columns (src, dst).
* Padding: invalid edges point at a *dump row* ``num_nodes`` (one extra row is
  allocated by callers where needed) or carry a zero in ``edge_mask`` /
  zero weight; reductions below always take an optional mask and zero the
  contribution of padded lanes, so results never depend on pad contents.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.segment_spmm.bitonic import bitonic_sort
from repro.kernels.segment_spmm.segment_spmm import CHUNK, sorted_segment_sum
from repro.obs import stages

Array = jax.Array

_NEG_INF = -1e30


def gather_src(x: Array, edges: Array) -> Array:
    """Features of the source endpoint of every edge: (E, F)."""
    return jnp.take(x, edges[:, 0], axis=0)


def gather_dst(x: Array, edges: Array) -> Array:
    """Features of the destination endpoint of every edge: (E, F)."""
    return jnp.take(x, edges[:, 1], axis=0)


def _masked(messages: Array, edge_mask: Array | None) -> Array:
    if edge_mask is None:
        return messages
    m = edge_mask.astype(messages.dtype)
    return messages * m.reshape(m.shape + (1,) * (messages.ndim - 1))


def scatter_sum(messages: Array, dst: Array, num_nodes: int,
                edge_mask: Array | None = None) -> Array:
    """Sum messages (E, ...) into per-node buckets (num_nodes, ...)."""
    return jax.ops.segment_sum(_masked(messages, edge_mask), dst,
                               num_segments=num_nodes)


def scatter_mean(messages: Array, dst: Array, num_nodes: int,
                 edge_mask: Array | None = None) -> Array:
    total = scatter_sum(messages, dst, num_nodes, edge_mask)
    ones = jnp.ones(messages.shape[:1], dtype=messages.dtype)
    cnt = jax.ops.segment_sum(_masked(ones, edge_mask), dst,
                              num_segments=num_nodes)
    cnt = jnp.maximum(cnt, 1.0)
    return total / cnt.reshape(cnt.shape + (1,) * (total.ndim - 1))


def scatter_max(messages: Array, dst: Array, num_nodes: int,
                edge_mask: Array | None = None) -> Array:
    if edge_mask is not None:
        m = edge_mask.reshape(edge_mask.shape + (1,) * (messages.ndim - 1))
        messages = jnp.where(m > 0, messages, _NEG_INF)
    out = jax.ops.segment_max(messages, dst, num_segments=num_nodes)
    # Nodes with no (valid) in-edges get -inf from segment_max; zero them.
    return jnp.where(out <= _NEG_INF / 2, 0.0, out)


def scatter_min(messages: Array, dst: Array, num_nodes: int,
                edge_mask: Array | None = None) -> Array:
    return -scatter_max(-messages, dst, num_nodes, edge_mask)


def scatter_std(messages: Array, dst: Array, num_nodes: int,
                edge_mask: Array | None = None, eps: float = 1e-5) -> Array:
    """Per-node population std of incoming messages (PNA aggregator)."""
    mean = scatter_mean(messages, dst, num_nodes, edge_mask)
    mean_sq = scatter_mean(messages * messages, dst, num_nodes, edge_mask)
    var = jnp.maximum(mean_sq - mean * mean, 0.0)
    return jnp.sqrt(var + eps)


def scatter_softmax(logits: Array, dst: Array, num_nodes: int,
                    edge_mask: Array | None = None) -> Array:
    """Numerically-stable per-destination softmax over edges (GAT-style)."""
    if edge_mask is not None:
        m = edge_mask.reshape(edge_mask.shape + (1,) * (logits.ndim - 1))
        logits = jnp.where(m > 0, logits, _NEG_INF)
    node_max = jax.ops.segment_max(logits, dst, num_segments=num_nodes)
    node_max = jnp.where(node_max <= _NEG_INF / 2, 0.0, node_max)
    shifted = logits - jnp.take(node_max, dst, axis=0)
    expd = jnp.exp(shifted)
    if edge_mask is not None:
        m = edge_mask.reshape(edge_mask.shape + (1,) * (expd.ndim - 1))
        expd = expd * m.astype(expd.dtype)
    denom = jax.ops.segment_sum(expd, dst, num_segments=num_nodes)
    denom = jnp.maximum(denom, 1e-16)
    return expd / jnp.take(denom, dst, axis=0)


def in_degree(edges: Array, num_nodes: int,
              edge_mask: Array | None = None) -> Array:
    ones = jnp.ones(edges.shape[:1], dtype=jnp.float32)
    if edge_mask is not None:
        ones = ones * edge_mask.astype(jnp.float32)
    return jax.ops.segment_sum(ones, edges[:, 1], num_segments=num_nodes)


def out_degree(edges: Array, num_nodes: int,
               edge_mask: Array | None = None) -> Array:
    ones = jnp.ones(edges.shape[:1], dtype=jnp.float32)
    if edge_mask is not None:
        ones = ones * edge_mask.astype(jnp.float32)
    return jax.ops.segment_sum(ones, edges[:, 0], num_segments=num_nodes)


def gcn_edge_weights(edges: Array, num_nodes: int,
                     edge_mask: Array | None = None,
                     edge_values: Array | None = None) -> Array:
    """Symmetric-normalized Laplacian edge weights (Eq. 1 of the paper).

    w(u, v) = val(u, v) / sqrt((1 + deg_u) (1 + deg_v)); the "+1" is the
    identity (self-loop) term of ``A + I``.  Self-loops themselves must be
    appended by the caller (``repro.graph.pad.add_self_loops``).
    """
    deg_in = in_degree(edges, num_nodes, edge_mask)
    deg_out = out_degree(edges, num_nodes, edge_mask)
    # Kipf-Welling uses the undirected degree; for directed snapshots we follow
    # the paper and use in/out degree on the respective endpoint.
    inv_sqrt_in = jax.lax.rsqrt(1.0 + deg_in)
    inv_sqrt_out = jax.lax.rsqrt(1.0 + deg_out)
    w = (jnp.take(inv_sqrt_out, edges[:, 0])
         * jnp.take(inv_sqrt_in, edges[:, 1]))
    if edge_values is not None:
        w = w * edge_values
    if edge_mask is not None:
        w = w * edge_mask.astype(w.dtype)
    return w


def _sort(key: Array, *payloads: Array) -> tuple[Array, ...]:
    """Lanes by ascending ``key``: the Pallas bitonic network on a TPU,
    whose code is a small fraction of XLA's sort's; XLA's sort elsewhere."""
    return jax.lax.platform_dependent(
        key, *payloads, tpu=bitonic_sort,
        default=lambda *a: tuple(jax.lax.sort(a, num_keys=1)))


def per_snapshot(fn):
    """``fn`` over arrays, batched by a loop over the batch rather than by
    batching rules, so that the Pallas kernels inside always run on one
    snapshot: their code stays that of the unbatched kernel."""
    batched = jax.custom_batching.custom_vmap(fn)

    @batched.def_vmap
    def _loop(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched, strict=True)]
        out = jax.lax.map(lambda a: batched(*a), args)
        return out, jax.tree.map(lambda _: True, out)

    return batched


def sorted_lanes(x: Array, frm: Array, to: Array, weights: Array,
                 num_rows: int) -> tuple[Array, Array]:
    """Lanes ``weights * x[frm]`` sorted by the row ``to`` they add into.

    Returns the ascending keys (L,) and the messages feature-major (F, L),
    with the E lanes padded to L, a positive multiple of the kernel's
    ``CHUNK``.  Zero-weight lanes add nothing: their key is ``num_rows``,
    so they sort to the end and fall out of range instead of piling onto
    one row.
    """
    e = weights.shape[0]
    pad = (0, max(-(-e // CHUNK), 1) * CHUNK - e)
    key = jnp.pad(jnp.where(weights != 0, to, num_rows), pad,
                  constant_values=num_rows)
    key, frm, weights = _sort(key, jnp.pad(frm, pad), jnp.pad(weights, pad))
    msgs = jnp.take(x, frm, axis=0, mode="clip").T * weights.astype(x.dtype)
    return key, msgs


def _sorted_sum(keys: Array, msgs: Array, num_rows: int) -> Array:
    return jax.ops.segment_sum(msgs.T, keys, num_segments=num_rows,
                               indices_are_sorted=True)


def _aggregate(x: Array, frm: Array, to: Array, weights: Array,
               num_rows: int) -> Array:
    """(num_rows, F): row r sums ``weights * x[frm]`` over lanes ``to == r``."""
    def one(x, frm, to, weights):
        keys, msgs = sorted_lanes(x, frm, to, weights, num_rows)
        return jax.lax.platform_dependent(
            keys, msgs.astype(jnp.float32),
            tpu=functools.partial(sorted_segment_sum, num_nodes=num_rows),
            default=functools.partial(_sorted_sum, num_rows=num_rows))
    return per_snapshot(one)(x, frm, to, weights).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def spmm(x: Array, edges: Array, edge_weights: Array, num_nodes: int) -> Array:
    """Sparse-dense product ``A_tilde @ x`` over destination-sorted lanes.

    ``edge_weights`` already folds in the Laplacian normalization and the edge
    mask (padded edges carry weight zero), which keeps this inner loop free of
    extra masking work.
    """
    with jax.named_scope(stages.SPMM):
        return _aggregate(x, edges[:, 0], edges[:, 1], edge_weights,
                          num_nodes)


def _spmm_fwd(x, edges, edge_weights, num_nodes):
    return spmm(x, edges, edge_weights, num_nodes), (x, edges, edge_weights)


def _spmm_bwd(_num_nodes, res, g):
    """``x``'s cotangent is the same sorted reduction with source and
    destination swapped; the weights' is the row dot ``<x[src], g[dst]>``,
    which XLA drops where nothing uses it."""
    x, edges, edge_weights = res
    with jax.named_scope(stages.SPMM):
        dx = _aggregate(g, edges[:, 1], edges[:, 0], edge_weights,
                        x.shape[0])
        dw = jnp.sum(gather_src(x, edges) * gather_dst(g, edges), axis=-1)
    return dx, None, dw.astype(edge_weights.dtype)


spmm.defvjp(_spmm_fwd, _spmm_bwd)
