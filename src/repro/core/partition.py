"""Data-distribution schemes for dynamic-GNN training (paper §4).

* ``snapshot_*``  — the paper's contribution (§4.2): shard the TIME axis; the
  GCN stage is communication-free, the temporal stage is reached through an
  all-to-all that re-shards T-major -> N-major and a second all-to-all back.
  Fixed O(T*N) volume per layer, for any P.
* ``vertex_*``    — the baseline (§4.1): shard the VERTEX axis; temporal stage
  is local but the GCN needs remote neighbor features.  Our regular-pattern
  implementation gathers the full frame (the dense upper bound of the
  hypergraph scheme); the analytic hypergraph volume is estimated separately
  in ``repro.dist.comm_volume``.
* ``hybrid``      — §6.5: snapshot groups x intra-snapshot sharding for
  snapshots too large for one device (used by the big static-graph cells).

All are written with ``shard_map`` so every collective is explicit and
auditable — the compiled HLO contains exactly the two all-to-alls per layer
that the paper counts.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.core import models as mdl
from repro.dist import compression as compression_lib
from repro.core import temporal
from repro.core.dtdg import DTDGBatch

Array = jax.Array


def _axis_size(mesh: Mesh, axis) -> int:
    if isinstance(axis, (tuple, list)):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


# ------------------------------------------------- snapshot partitioning ----

def _major_all_to_all(y: Array, axis, num_procs: int,
                      split_axis: int) -> Array:
    """The tiled all-to-all between the T-sharded (``split_axis=1``:
    (Ts, N, F) -> (Ts*P, N/P, F)) and N-sharded (``split_axis=0``: the
    inverse) layouts of a block, exchanged along a MAJOR axis.

    Same result as ``lax.all_to_all(y, axis, split_axis, 1 - split_axis,
    tiled=True)``.  The chunks are split off a leading (P, ...) or (1, ...)
    axis instead of the vertex axis: the reshapes only split or merge
    major dims, so no relayout is needed.  Splitting the vertex axis of a
    narrow-feature (N, 6) array directly makes the TPU compiler emit code
    linear in N — about 5 minutes and 200 MB of code per all-to-all at
    N=755,200, against seconds this way.
    """
    t, n, f = y.shape
    if split_axis == 1:
        y = jax.lax.all_to_all(y.reshape(t, num_procs, n // num_procs, f),
                               axis, split_axis=1, concat_axis=0, tiled=True)
        return y.reshape(t * num_procs, n // num_procs, f)
    y = jax.lax.all_to_all(y.reshape(t, 1, n, f), axis, split_axis=0,
                           concat_axis=1, tiled=True)
    return y.reshape(t // num_procs, n * num_procs, f)


def _sp_block_body(cfg: mdl.DynGNNConfig, params: dict, axis,
                   num_procs: int, carries: list, blk,
                   comm_dtype=None, fused_labels: bool = False,
                   a2a_chunks: int = 1, compression: str = "none",
                   comm_residuals: list | None = None):
    """One checkpoint block under snapshot partitioning (Fig. 3b).

    Local shapes: x (bsize/P, N, F); temporal carries are vertex-sharded
    (N/P rows).  Returns T-sharded block output (bsize/P, N, out).

    Beyond-paper options (§Perf iteration on the paper's own workload):
      * ``comm_dtype`` — cast all-to-all payloads (e.g. bf16): halves the
        redistribution volume; compute stays in the working dtype.
      * ``fused_labels`` — blk carries labels in the VERTEX-sharded layout
        (bsize, N/P); the final layer's loss is computed there and the last
        N->T redistribution is skipped entirely (the classifier is
        per-(t, u), so the loss decomposes over vertex shards).  Removes
        1 of the 2L all-to-alls per block.
      * ``compression`` != "none" — int8 error-feedback quantization of
        both redistributions (dist.compression.make_quantized_a2a).
        ``comm_residuals`` must then carry one (res_t2n, res_n2t) pair
        per layer in the PRE-a2a layouts (see ``a2a_payload_dims``), and
        the body returns ``(new_carries, h, new_comm_residuals)``.
    """
    if fused_labels:
        x_b, e_b, w_b, t0, labels_b = blk
    else:
        x_b, e_b, w_b, t0 = blk
        labels_b = None
    compression_lib.validate_mode(compression)
    compress = compression_lib.compresses_a2a(compression)
    if compress:
        if comm_dtype is not None or fused_labels:
            raise ValueError(
                "compression composes with a2a_chunks only, not with "
                "comm_dtype/fused_labels")
        if comm_residuals is None:
            raise ValueError(
                "compression != 'none' requires comm_residuals "
                "(init_comm_residuals)")
    p_idx = jax.lax.axis_index(axis)
    bsl = x_b.shape[0]                      # bsize / P local steps
    evolve = cfg.model == "evolvegcn"

    def _feature_cuts(width):
        return [width * c // a2a_chunks for c in range(1, a2a_chunks)]

    def a2a(y, split_axis):
        orig = y.dtype
        if comm_dtype is not None:
            y = y.astype(comm_dtype)
        if a2a_chunks > 1:
            # §6.5 overlap schedule: C independent all-to-alls over feature
            # slices, so the scheduler can run chunk c's redistribution
            # concurrently with chunk c-1's consumer compute.
            pieces = [_major_all_to_all(p, axis, num_procs, split_axis)
                      for p in jnp.split(y, _feature_cuts(y.shape[-1]),
                                         axis=-1)]
            y = jnp.concatenate(pieces, axis=-1)
        else:
            y = _major_all_to_all(y, axis, num_procs, split_axis)
        return y.astype(orig)

    def a2a_q(y, res, split_axis, concat_axis):
        # int8 redistribution with per-shard error feedback; chunking
        # slices payload AND residual with the same feature cuts so each
        # chunk keeps its own absmax scales.
        qa = compression_lib.make_quantized_a2a(axis, num_procs,
                                                split_axis, concat_axis)
        if a2a_chunks > 1:
            cuts = _feature_cuts(y.shape[-1])
            outs = [qa(yp, rp)
                    for yp, rp in zip(jnp.split(y, cuts, axis=-1),
                                      jnp.split(res, cuts, axis=-1))]
            return (jnp.concatenate([o for o, _ in outs], axis=-1),
                    jnp.concatenate([r for _, r in outs], axis=-1))
        return qa(y, res)

    h = x_b
    new_carries = []
    new_comm_res = []
    loss_contrib = None
    for l in range(cfg.num_layers):
        last = l == cfg.num_layers - 1
        lp = params["layers"][l]
        # --- spatial stage: communication-free (whole snapshots local) -----
        if evolve:
            # every processor redundantly evolves the block's weights from the
            # carried block-boundary state (weights are tiny — §5.5), then
            # slices its own bsl steps.
            w_prev, st = carries[l]
            ws, w_last, st_last = temporal.evolve_weights_from(
                lp["evolve"], w_prev, st, bsl * num_procs)
            ws_local = jax.lax.dynamic_slice_in_dim(ws, p_idx * bsl, bsl, 0)

            def per_step(xt, et, wt, w_t):
                y0 = mdl.gcnlib.spatial_aggregate(xt, et, wt, xt.shape[0],
                                                  cfg.use_pallas)
                return jax.nn.relu(y0 @ w_t)

            h = jax.vmap(per_step)(h, e_b, w_b, ws_local)
            new_carries.append((w_last, st_last))
            # EvolveGCN's temporal op acts on weights -> feature path needs
            # NO redistribution (the model is communication-free, §5.5).
            continue

        h, _ = mdl.spatial_stage(cfg, lp, l, h, e_b, w_b, None, t0)
        # --- redistribution 1: T-sharded -> N-sharded (all-to-all) ---------
        if compress:
            res_t2n, res_n2t = comm_residuals[l]
            h, nr1 = a2a_q(h, res_t2n, split_axis=1, concat_axis=0)
        else:
            h = a2a(h, split_axis=1)
        # --- temporal stage: full block timeline, local vertices -----------
        h, c_tm = mdl.temporal_stage(cfg, lp, l, h, carries[l], t0)
        new_carries.append(c_tm)
        if last and labels_b is not None:
            # fused loss in the vertex-sharded domain; no final a2a
            logits = mdl.classify(params, h)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(logp, labels_b[..., None],
                                       axis=-1)[..., 0]
            loss_contrib = jnp.sum(nll)
            return new_carries, loss_contrib
        # --- redistribution 2: N-sharded -> T-sharded ----------------------
        if compress:
            h, nr2 = a2a_q(h, res_n2t, split_axis=0, concat_axis=1)
            new_comm_res.append((nr1, nr2))
        else:
            h = a2a(h, split_axis=0)
    if compress:
        # evolvegcn redistributes nothing, so new_comm_res is [] there
        return new_carries, h, new_comm_res
    return new_carries, h


# Public alias: one checkpoint block of the sharded layer stack (carries in,
# carries out).  The streamed distributed trainer (repro.stream.distributed)
# reuses it directly so the online path shares every collective with the
# offline shard_map path above.
snapshot_block_body = _sp_block_body


def a2a_payload_dims(cfg: mdl.DynGNNConfig) -> list[tuple[int, int]]:
    """Per-layer feature widths ``(f_t2n, f_n2t)`` of the two
    redistributions in ``snapshot_block_body``.

    The T->N payload is the spatial-stage output (cdgcn concatenates the
    aggregate with the GCN transform, so it is ``d_in + d_gcn`` wide);
    the N->T payload is the temporal-stage output.  EvolveGCN
    redistributes nothing (§5.5) — empty list.
    """
    if cfg.model == "evolvegcn":
        return []
    return [(d_in + d_gcn if cfg.model == "cdgcn" else d_out, d_out)
            for d_in, d_gcn, d_out in cfg.layer_dims()]


def snapshot_partition_forward(cfg: mdl.DynGNNConfig, mesh: Mesh,
                               axis="data", a2a_chunks: int = 1):
    """Build the sharded forward fn: (params, batch) -> Z (T-sharded).

    Block layout: arrays are (nb, bsize, ...) with the *bsize* axis sharded,
    so each processor owns contiguous steps within each block (Fig. 3b).
    ``a2a_chunks > 1`` chunks every redistribution into that many
    feature-sliced all-to-alls (the §6.5 overlap schedule; math-identical).
    """
    num_procs = _axis_size(mesh, axis)
    nb = cfg.checkpoint_blocks

    def fn(params, frames, edges, ew):
        # local: frames (nb, bsize/P, N, F)
        bsl = frames.shape[1]
        n_local = cfg.num_nodes // num_procs
        carries = mdl.init_carries(cfg, params, num_local_nodes=n_local,
                                   dtype=frames.dtype)
        t0s = jnp.arange(nb, dtype=jnp.int32) * (bsl * num_procs)
        body = jax.checkpoint(
            partial(_sp_block_body, cfg, params, axis, num_procs,
                    a2a_chunks=a2a_chunks),
            prevent_cse=True)
        _, zs = jax.lax.scan(body, carries, (frames, edges, ew, t0s))
        return zs                     # (nb, bsize/P, N, out) local

    spec_b = P(None, axis)          # (nb, bsize<split>, ...)
    return shard_map(
        fn, mesh=mesh,
        in_specs=(P(), spec_b, spec_b, spec_b),
        out_specs=spec_b,
        check_vma=False)


def snapshot_partition_loss(cfg: mdl.DynGNNConfig, mesh: Mesh, axis="data",
                            comm_dtype=None, fuse_final: bool = False,
                            a2a_chunks: int = 1):
    """Sharded scalar loss: mean CE over all (t, u).

    fuse_final (beyond-paper): labels ride VERTEX-sharded (nb, bsize, N/P)
    and the final N->T all-to-all is elided; comm_dtype casts the remaining
    redistributions (see _sp_block_body); a2a_chunks splits every
    redistribution into that many feature-sliced all-to-alls (the §6.5
    overlap schedule; math-identical).  All default off = the
    paper-faithful execution.
    """
    num_procs = _axis_size(mesh, axis)
    nb = cfg.checkpoint_blocks
    fuse = fuse_final and cfg.model != "evolvegcn"

    def fn(params, frames, edges, ew, labels):
        bsl = frames.shape[1]
        n_local = cfg.num_nodes // num_procs
        carries = mdl.init_carries(cfg, params, num_local_nodes=n_local,
                                   dtype=frames.dtype)
        t0s = jnp.arange(nb, dtype=jnp.int32) * (bsl * num_procs)
        body = jax.checkpoint(
            partial(_sp_block_body, cfg, params, axis, num_procs,
                    comm_dtype=comm_dtype, fused_labels=fuse,
                    a2a_chunks=a2a_chunks),
            prevent_cse=True)
        if fuse:
            _, nll_sums = jax.lax.scan(
                body, carries, (frames, edges, ew, t0s, labels))
            total = jax.lax.psum(jnp.sum(nll_sums), axis)
            count = jnp.asarray(nb * bsl * num_procs * cfg.num_nodes,
                                jnp.float32)
            return total / count
        _, zs = jax.lax.scan(body, carries, (frames, edges, ew, t0s))
        z = zs.reshape((nb * bsl,) + zs.shape[2:])     # (T/P, N, F')
        logits = mdl.classify(params, z)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        lab = labels.reshape((nb * bsl,) + labels.shape[2:])
        nll = -jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
        total = jax.lax.psum(jnp.sum(nll), axis)
        count = jax.lax.psum(jnp.asarray(nll.size, jnp.float32), axis)
        return total / count

    spec_b = P(None, axis)
    label_spec = P(None, None, axis) if fuse else spec_b
    return shard_map(
        fn, mesh=mesh,
        in_specs=(P(), spec_b, spec_b, spec_b, label_spec),
        out_specs=P(),
        check_vma=False)


def blockify_batch(batch: DTDGBatch, nb: int) -> tuple:
    """Host-side reshape of a DTDG batch to (nb, bsize, ...) arrays."""
    def blk(a):
        t = a.shape[0]
        return a.reshape((nb, t // nb) + a.shape[1:])
    return (blk(batch.frames), blk(batch.edges), blk(batch.edge_weights))


# --------------------------------------------------- vertex partitioning ----

def vertex_partition_forward(cfg: mdl.DynGNNConfig, mesh: Mesh, axis="data"):
    """Baseline §4.1: vertices sharded; GCN gathers remote features.

    Edges are pre-partitioned by destination shard on the host (each device
    receives the edges whose dst it owns, with GLOBAL src ids and LOCAL dst
    ids).  Per snapshot the device all-gathers the frame (the regular-pattern
    upper bound of vertex partitioning — volume grows ~P, unlike snapshots).
    The temporal stage is local, as in the paper.
    """
    num_procs = _axis_size(mesh, axis)

    def fn(params, frames, edges, ew):
        # local: frames (T, N/P, F); edges (T, E/P, 2) [src global, dst local]
        n_local = frames.shape[1]
        evolve = cfg.model == "evolvegcn"
        carries = mdl.init_carries(cfg, params, num_local_nodes=n_local,
                                   dtype=frames.dtype)
        h = frames
        new_carries = []
        for l in range(cfg.num_layers):
            lp = params["layers"][l]
            # all-gather the frame so every src row is addressable: this is
            # the irregular-neighbor-exchange, upper-bounded regularly.
            h_full = jax.lax.all_gather(h, axis, axis=1, tiled=True)

            def agg(xt_full, et, wt):
                msgs = jnp.take(xt_full, et[:, 0], axis=0) \
                    * wt[:, None].astype(xt_full.dtype)
                return jax.ops.segment_sum(msgs, et[:, 1],
                                           num_segments=n_local)

            if evolve:
                w_prev, st = carries[l]
                ws, w_last, st_last = temporal.evolve_weights_from(
                    lp["evolve"], w_prev, st, h.shape[0])
                y0 = jax.vmap(agg)(h_full, edges, ew)
                h = jax.nn.relu(jnp.einsum("tnf,tfg->tng", y0, ws))
                new_carries.append((w_last, st_last))
                continue
            y0 = jax.vmap(agg)(h_full, edges, ew)
            if cfg.model == "cdgcn":
                y1 = y0 @ lp["gcn"]["w"] + lp["gcn"]["b"]
                h2 = jax.nn.relu(jnp.concatenate([y0, y1], axis=-1))
            else:
                h2 = jax.nn.relu(y0 @ lp["gcn"]["w"] + lp["gcn"]["b"])
            h, c_tm = mdl.temporal_stage(cfg, lp, l, h2, carries[l], 0)
            new_carries.append(c_tm)
        return h

    return shard_map(
        fn, mesh=mesh,
        in_specs=(P(), P(None, axis), P(None, axis), P(None, axis)),
        out_specs=P(None, axis),
        check_vma=False)


def partition_edges_by_dst(edges_padded, masks, num_nodes: int,
                           num_procs: int, max_local_edges: int):
    """Host-side dst-shard edge partitioning for the vertex baseline.

    Returns (T, P, E_loc, 2) with src GLOBAL / dst LOCAL ids and the matching
    mask, ready to be fed shard-wise.
    """
    import numpy as np
    t_steps = edges_padded.shape[0]
    n_per = num_nodes // num_procs
    out_e = np.zeros((t_steps, num_procs, max_local_edges, 2), dtype=np.int32)
    out_w = np.zeros((t_steps, num_procs, max_local_edges), dtype=np.float32)
    for t in range(t_steps):
        e = np.asarray(edges_padded[t])
        m = np.asarray(masks[t]) > 0
        e = e[m]
        w = np.asarray(masks[t])[m]
        owner = e[:, 1] // n_per
        for p in range(num_procs):
            sel = e[owner == p]
            wsel = w[owner == p]
            k = min(sel.shape[0], max_local_edges)
            out_e[t, p, :k, 0] = sel[:k, 0]
            out_e[t, p, :k, 1] = sel[:k, 1] % n_per
            out_w[t, p, :k] = wsel[:k]
    return out_e, out_w


# -------------------------------------------------------------- hybrid ------

def hybrid_spmm(x: Array, edges: Array, edge_weights: Array,
                num_nodes: int, model_axis="model") -> Array:
    """§6.5 hybrid partitioning: intra-snapshot edge sharding.

    Called under shard_map with edges sharded over ``model_axis`` and x
    replicated within the group: each shard computes a partial segment-sum
    over its edge slice; a psum over the group completes the aggregate.
    Enables snapshots too large for one device (AMLSim-Large experiment).
    """
    msgs = jnp.take(x, edges[:, 0], axis=0) \
        * edge_weights[:, None].astype(x.dtype)
    partial_sum = jax.ops.segment_sum(msgs, edges[:, 1],
                                      num_segments=num_nodes)
    return jax.lax.psum(partial_sum, model_axis)
