"""Per-snapshot streaming training over the delta stream.

The regime the transfer pipeline exists for: snapshots arrive one delta at
a time, the device reconstructs the padded edge list (``apply_delta``),
recomputes the Laplacian weights from the reconstructed topology
(degree-derived — only index deltas + raw values cross the link, §5.5),
and runs one online train step per snapshot, threading the models'
temporal carries across steps.

Two drivers share every jitted computation and consume the items in the
same order, so their loss streams are BIT-IDENTICAL:

* ``overlap=False`` — the synchronous reference: encode, transfer, and
  compute strictly interleaved on one thread;
* ``overlap=True``  — encode + ``device_put`` run on the prefetch thread,
  ``depth`` deltas ahead of the compute stream.

The overlap path's win is measured in ``benchmarks/overlap_bench.py``.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import models as mdl
from repro.graph import segment
from repro.obs import stages
from repro.optim import adamw
from repro.stream import encoder as enc
from repro.stream.prefetch import (DeltaApplier, PrefetchIterator,
                                   SlotStacker, stage_item)


@dataclass
class StreamTrainState:
    params: dict
    opt_state: dict
    losses: list


def advance_slice(cfg: mdl.DynGNNConfig, params: dict, carries: list,
                  frames, edges, mask, values,
                  t_offset) -> tuple[jax.Array, list]:
    """The STATE-ADVANCE step: one time-window of reconstructed snapshots
    rolls the temporal carries forward and yields the window's embeddings.

    frames (k, N, F), edges (k, E, 2), mask/values (k, E) -> (z (k, N, F'),
    new carries).  This is the forward math every consumer of the delta
    stream shares — the per-snapshot/slice TRAINING steps below wrap it in
    a loss + AdamW update, the online SERVING engine
    (``repro.serve.state.make_advance_step``) jits it alone with donated
    carries.  Keeping it single-sourced is what pins served scores to the
    offline training reference."""
    e_full, w_full = slice_weights_with_loops(
        cfg.num_nodes, *make_self_loops(cfg.num_nodes), edges, mask, values)
    return mdl.forward_slice(cfg, params, frames, e_full, w_full, carries,
                             t_offset)


def make_stream_train_step(cfg: mdl.DynGNNConfig,
                           opt_cfg: adamw.AdamWConfig):
    """Jitted per-snapshot step: reconstructed (edges, mask, values) ->
    Laplacian weights on device -> one-layer-stack forward over the
    length-1 timeline slice (``advance_slice``) -> CE loss -> AdamW
    update."""

    @jax.jit
    def step(params, opt_state, carries, frame, edges, mask, values,
             labels, t_offset):
        def loss_fn(p):
            z, new_carries = advance_slice(cfg, p, carries, frame[None],
                                           edges[None], mask[None],
                                           values[None], t_offset)
            return mean_nll(p, z[0], labels), new_carries

        (loss, new_carries), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        params2, opt2 = adamw.apply_updates(opt_cfg, params, grads,
                                            opt_state)
        return params2, opt2, new_carries, loss

    return step


@jax.named_scope(stages.EDGE_WEIGHTS)
def make_self_loops(n: int) -> tuple[jax.Array, jax.Array]:
    """Device-resident self-loop edge list + unit mask/values for N nodes."""
    return (jnp.stack([jnp.arange(n, dtype=jnp.int32)] * 2, axis=1),
            jnp.ones((n,), dtype=jnp.float32))


@jax.named_scope(stages.EDGE_WEIGHTS)
def slice_weights_with_loops(n: int, loop_edges, loop_ones, edges, mask,
                             values) -> tuple[jax.Array, jax.Array]:
    """Append self-loops to a (k, E, 2) slice of reconstructed snapshots
    and recompute the per-step Laplacian weights on device.

    The ONE implementation of the streamed loss preamble — the
    single-device slice step and the sharded block step (where ``edges``
    is each shard's local time slice) both call it, so the <=1e-5 pinned
    equivalence can't drift apart edit by edit.
    """
    k = edges.shape[0]
    le = jnp.broadcast_to(loop_edges[None], (k,) + loop_edges.shape)
    lo = jnp.broadcast_to(loop_ones[None], (k,) + loop_ones.shape)
    e_full = jnp.concatenate([edges, le], axis=1)
    m_full = jnp.concatenate([mask, lo], axis=1)
    v_full = jnp.concatenate([values, lo], axis=1)
    w_full = jax.vmap(
        lambda e, m, v: segment.gcn_edge_weights(e, n, m, v))(
        e_full, m_full, v_full)
    return e_full, w_full


@jax.named_scope(stages.LOSS)
def slice_nll(params: dict, z, labels) -> jax.Array:
    """Per-(t, u) CE against the shared classifier (float32 softmax)."""
    logits = mdl.classify(params, z)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


@jax.named_scope(stages.LOSS)
def mean_nll(params: dict, z, labels) -> jax.Array:
    """The streamed steps' loss: mean CE over every (t, u)."""
    return jnp.mean(slice_nll(params, z, labels))


def make_stream_slice_step(cfg: mdl.DynGNNConfig,
                           opt_cfg: adamw.AdamWConfig):
    """Jitted multi-snapshot step over a contiguous timeline slice.

    Same math as ``make_stream_train_step`` generalized to ``k`` stacked
    reconstructed snapshots: per-step Laplacian weights on device, one
    ``forward_slice`` over the k-length timeline, mean CE, one AdamW
    update.  This is the single-device reference the snapshot-parallel
    distributed streamed trainer (``repro.stream.distributed``) must match:
    there the identical slice is computed with the time axis sharded and
    the temporal stage reached through two all-to-alls.
    """

    @jax.jit
    def step(params, opt_state, carries, frames, edges, mask, values,
             labels, t_offset):
        def loss_fn(p):
            z, new_carries = advance_slice(cfg, p, carries, frames, edges,
                                           mask, values, t_offset)
            return mean_nll(p, z, labels), new_carries

        (loss, new_carries), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        params2, opt2 = adamw.apply_updates(opt_cfg, params, grads,
                                            opt_state)
        return params2, opt2, new_carries, loss

    return step


def host_stream(snapshots, values, frames, labels, num_nodes: int,
                max_edges: int, block_size: int,
                stats: enc.DeltaStats | None = None,
                report: enc.StreamReport | None = None,
                encoded=None):
    """Host iterator of (delta item, frame_t, labels_t) per step;
    ``encoded`` replays already encoded items instead of encoding."""
    it = (iter(encoded) if encoded is not None else
          enc.iter_encode_stream(snapshots, values, num_nodes, max_edges,
                                 block_size, stats, report=report))
    for t, item in enumerate(it):
        yield (item, np.asarray(frames[t]), np.asarray(labels[t]))


def default_max_edges(snapshots) -> int:
    return enc.padded_max_edges(snapshots)


def round_host_stream(step_iter, slice_len: int):
    """Group the per-step host stream into slices of ``slice_len``:
    yields (items tuple, frames (k, N, F), labels (k, N)) per round."""
    items, frs, labs = [], [], []
    for item, fr, lab in step_iter:
        items.append(item)
        frs.append(fr)
        labs.append(lab)
        if len(items) == slice_len:
            yield tuple(items), np.stack(frs), np.stack(labs)
            items, frs, labs = [], [], []
    if items:
        raise ValueError(f"trace length not divisible by slice_len="
                         f"{slice_len} ({len(items)} steps left over)")


def train_streamed(cfg: mdl.DynGNNConfig, snapshots, values, frames,
                   labels, *, block_size: int | None = None,
                   num_epochs: int = 1, overlap: bool = True,
                   prefetch_depth: int = 2,
                   opt_cfg: adamw.AdamWConfig | None = None,
                   params: dict | None = None, opt_state=None,
                   stats: enc.DeltaStats | None = None,
                   max_edges: int | None = None,
                   slice_len: int | None = None,
                   report: enc.StreamReport | None = None,
                   encoded=None,
                   step_fn=None,
                   seed: int = 0,
                   log_every: int = 10,
                   log_fn=None) -> StreamTrainState:
    """Stream the trace through per-snapshot training.

    Identical-loss guarantee: for fixed inputs the returned loss sequence
    does not depend on ``overlap`` / ``prefetch_depth`` — prefetching moves
    work between threads, never across the data dependency order.

    ``slice_len`` > 1 switches to slice-granularity online updates: each
    round reconstructs ``slice_len`` consecutive snapshots from the delta
    stream and takes ONE AdamW step on their mean CE (the single-device
    reference semantics of the distributed streamed trainer, which shards
    exactly this slice over its mesh).  ``slice_len`` in (None, 1) keeps
    the per-snapshot schedule unchanged.

    ``encoded`` is the trace as ``enc.iter_encode_stream`` gives it for
    these (stats, max_edges, block_size); each epoch then stages those
    items instead of encoding the trace anew.

    ``step_fn`` lets callers that invoke this in a loop (the Engine's
    streamed worker, benchmark epochs) reuse one compiled step instead of
    re-tracing per call; it must come from ``make_stream_train_step``
    (or ``make_stream_slice_step`` when sliced) with matching
    (cfg, opt_cfg).
    """
    t_steps = len(snapshots)
    block_size = block_size or max(t_steps // max(cfg.checkpoint_blocks, 1),
                                   1)
    max_edges = max_edges or default_max_edges(snapshots)
    if stats is None:
        stats = enc.measure_stats(snapshots, cfg.num_nodes, block_size,
                                  max_edges)
    opt_cfg = opt_cfg or adamw.AdamWConfig(
        lr=1e-2, warmup_steps=10, total_steps=num_epochs * t_steps,
        weight_decay=0.0)
    if params is None:
        params = mdl.init_params(jax.random.PRNGKey(seed), cfg)
    if opt_state is None:
        opt_state = adamw.init_state(params)
    sliced = slice_len is not None and slice_len > 1
    if step_fn is None:
        step_fn = (make_stream_slice_step(cfg, opt_cfg) if sliced
                   else make_stream_train_step(cfg, opt_cfg))
    mk_host = partial(host_stream, snapshots, values, frames, labels,
                      cfg.num_nodes, max_edges, block_size, stats, report,
                      encoded)
    if sliced and t_steps % slice_len:
        raise ValueError(f"slice_len {slice_len} must divide the trace "
                         f"length {t_steps}")

    losses: list[float] = []
    trc = obs.get_tracer()
    for epoch in range(num_epochs):
        items = None
        with contextlib.ExitStack() as epoch_start:
            # open from the epoch's start until its first item is in hand:
            # the new prefetch worker's first encode + transfer, the ring
            # and the carries
            epoch_start.enter_context(
                trc.span("stream.epoch_start", epoch=epoch))
            try:
                host = round_host_stream(mk_host(), slice_len) if sliced \
                    else mk_host()
                if overlap:
                    items = PrefetchIterator(host, depth=prefetch_depth)
                else:
                    items = (stage_item(x) for x in host)
                applier = DeltaApplier(max_edges)
                carries = mdl.init_carries(cfg, params)
                stacker = SlotStacker(slice_len) if sliced else None
                for t, (item, frame, lab) in enumerate(items):
                    if t == 0:
                        epoch_start.close()
                    with trc.span("stream.apply", epoch=epoch, step=t):
                        if sliced:
                            for j, sub in enumerate(item):
                                stacker.put(j, *applier.consume(sub))
                            edges, mask, vals = stacker.arrays()
                        else:
                            edges, mask, vals = applier.consume(item)
                    with trc.span("stream.step", epoch=epoch, step=t):
                        params, opt_state, carries, loss = step_fn(
                            params, opt_state, carries, frame, edges, mask,
                            vals, lab, jnp.int32(t * (slice_len if sliced
                                                      else 1)))
                    # the step's completion record: its loss reached the host
                    with trc.span("stream.sync", epoch=epoch, step=t):
                        losses.append(float(loss))
                    obs.inc("stream.steps")
                    if log_fn is not None \
                            and (len(losses) - 1) % log_every == 0:
                        log_fn(f"stream {'slice' if sliced else 'step'} "
                               f"{len(losses) - 1} loss {losses[-1]:.4f}")
            finally:
                # unblock + retire the prefetch worker if the step raised
                if isinstance(items, PrefetchIterator):
                    items.close()
    return StreamTrainState(params=params, opt_state=opt_state,
                            losses=losses)
