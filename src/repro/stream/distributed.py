"""Distributed streamed training: per-shard delta streams under the
fixed-volume snapshot distribution (paper §3.2 x §4.2, composed).

This is where the two transfer subsystems finally meet the compute
distribution the paper benchmarks:

* ``stream/sharded.py`` cuts the delta stream into self-contained
  time-slice streams — shard s receives ONLY the deltas of the snapshots
  it owns (payload ~1/P per device);
* each shard feeds its own ``DeltaApplier`` edge-buffer ring, pinned to
  its device, reconstructing its slice of every round on device;
* the prefetch thread stages each shard's next round with its
  per-device / NamedSharding placement while the current round trains;
* one round = one checkpoint block of ``win`` snapshots: the jitted train
  step runs the snapshot-parallel ``shard_map``
  (``core.partition.snapshot_block_body``) over the assembled
  time-sharded arrays, so the GCN stage is communication-free and the
  temporal stage crosses shards through the paper's two fixed-volume
  all-to-alls per layer.

Loss semantics match ``train_loop.train_streamed(slice_len=win)`` exactly
(same slice, same mean CE, same AdamW cadence); the equivalence is pinned
to <= 1e-5 relative in ``tests/test_dist_stream.py``.

Two further schedule knobs pipeline the round itself (losses unchanged —
the pinned tests cover every combination; see docs/architecture.md for
the round diagram):

* ``a2a_chunks=C`` chunks each of the two per-layer redistributions into
  C feature-sliced all-to-alls (``partition.snapshot_block_body``), so
  chunk c's transfer can overlap chunk c-1's consumer compute;
* ``pipeline_rounds=True`` double-buffers the per-shard edge rings and
  keeps ONE round in flight: round r+1's delta-apply + staging is
  dispatched before round r's loss is forced to the host, so the
  reconstruction work runs concurrently with round r's temporal-stage
  collectives.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro import obs
from jax import shard_map
from repro.core import models as mdl
from repro.core import partition
from repro.dist import compression as compression_lib
from repro.dist import sharding as shardlib
from repro.ft.straggler import StepTimer
from repro.optim import adamw
from repro.stream import encoder as enc
from repro.stream import sharded as stream_sharded
from repro.stream import train_loop as tl
from repro.stream.prefetch import (DeltaApplier, PrefetchIterator,
                                   SlotStacker, stage_item)

P = partition.P


@dataclass
class DistStreamState:
    params: dict
    opt_state: dict
    losses: list
    per_shard_bytes: list = field(default_factory=list)
    carries: object = None          # final temporal carries (mesh-sharded)
    step_timer: object = None       # the run's StepTimer (EWMA watchdog)


def make_dist_stream_step(cfg: mdl.DynGNNConfig, mesh,
                          opt_cfg: adamw.AdamWConfig, axis: str = "data",
                          a2a_chunks: int = 1,
                          num_seeds: int | None = None,
                          compression: str = "none"):
    """Jitted per-round step: time-sharded reconstructed snapshots ->
    Laplacian weights on each shard -> snapshot-parallel block body
    (2 all-to-alls per layer) -> replicated mean CE -> AdamW update.

    Carries thread across rounds OUTSIDE the shard_map: feature-RNN
    carries stay vertex-sharded on the mesh between calls (they live in
    the N-sharded domain the temporal stage runs in), EvolveGCN's weight
    carry stays replicated.

    ``a2a_chunks=C`` splits each redistribution into C feature-sliced
    all-to-alls (the §6.5 overlap schedule) — math-identical, so the
    loss stream is pinned to the C=1 reference.

    ``num_seeds`` is the sampled schedule's loss restriction
    (``repro.hoststore``): the vertex axis is then a round-local node
    TABLE whose first ``num_seeds`` lanes are the seed batch, and only
    those lanes carry loss (mean over seeds).  ``None`` (full-graph
    schedules) keeps the all-vertices mean.

    ``compression`` != "none" quantizes the redistributions to int8 with
    per-shard error feedback (``dist.compression``).  The step then takes
    the residual tree as a 4th argument (after carries, see
    ``init_comm_residuals``) and returns it updated:
    ``(params, opt_state, carries, comm_res, loss)``.  With "none" the
    signature and jaxpr are exactly today's — bit-identical losses.
    """
    if a2a_chunks < 1:
        raise ValueError(f"a2a_chunks must be >= 1, got {a2a_chunks}")
    compression_lib.validate_mode(compression)
    num_procs = mesh.shape[axis]
    n = cfg.num_nodes
    if n % num_procs:
        raise ValueError(f"num_nodes {n} must divide over {num_procs} "
                         f"snapshot shards (vertex-sharded temporal stage)")
    if num_seeds is not None and not 1 <= num_seeds <= n:
        raise ValueError(f"num_seeds {num_seeds} must lie in [1, {n}]")
    loop_edges, loop_ones = tl.make_self_loops(n)
    carry_specs = shardlib.stream_carry_specs(cfg, axis)
    b = shardlib.stream_batch_specs(axis)

    def _loss_tail(nll, bsl):
        if num_seeds is None:
            total = jax.lax.psum(jnp.sum(nll), axis)
            count = jnp.asarray(bsl * num_procs * n, jnp.float32)
        else:
            seed_mask = (jnp.arange(n) < num_seeds).astype(nll.dtype)
            total = jax.lax.psum(jnp.sum(nll * seed_mask[None, :]), axis)
            count = jnp.asarray(bsl * num_procs * num_seeds, jnp.float32)
        return total / count

    if compression == "none":
        def sharded_loss(params, carries, frames, edges, mask, values,
                         labels, t0):
            # local: frames (win/P, N, F); edges (win/P, E, 2);
            # labels (win/P, N)
            bsl = frames.shape[0]
            # same preamble as the single-device slice step, on the local
            # slice (per-snapshot Laplacian weights: no collectives)
            e_full, w_full = tl.slice_weights_with_loops(
                n, loop_edges, loop_ones, edges, mask, values)
            new_carries, h = partition.snapshot_block_body(
                cfg, params, axis, num_procs, carries,
                (frames, e_full, w_full, t0), a2a_chunks=a2a_chunks)
            nll = tl.slice_nll(params, h, labels)
            return _loss_tail(nll, bsl), new_carries

        loss_fn = shard_map(
            sharded_loss, mesh=mesh,
            in_specs=(P(), carry_specs, b["frames"], b["edges"], b["mask"],
                      b["values"], b["labels"], P()),
            out_specs=(P(), carry_specs),
            check_vma=False)

        @jax.jit
        def step(params, opt_state, carries, frames, edges, mask, values,
                 labels, t0):
            (loss, new_carries), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, carries, frames, edges, mask,
                                       values, labels, t0)
            params2, opt2 = adamw.apply_updates(opt_cfg, params, grads,
                                                opt_state)
            return params2, opt2, new_carries, loss

        return step

    res_specs = shardlib.stream_comm_residual_specs(cfg, axis)

    def sharded_loss_q(params, carries, comm_res, frames, edges, mask,
                       values, labels, t0):
        bsl = frames.shape[0]
        e_full, w_full = tl.slice_weights_with_loops(
            n, loop_edges, loop_ones, edges, mask, values)
        new_carries, h, new_res = partition.snapshot_block_body(
            cfg, params, axis, num_procs, carries,
            (frames, e_full, w_full, t0), a2a_chunks=a2a_chunks,
            compression=compression, comm_residuals=comm_res)
        nll = tl.slice_nll(params, h, labels)
        # new_res rides the aux output: value_and_grad gives it a zero
        # cotangent, matching the non-differentiable residual carry.
        return _loss_tail(nll, bsl), (new_carries, new_res)

    loss_fn = shard_map(
        sharded_loss_q, mesh=mesh,
        in_specs=(P(), carry_specs, res_specs, b["frames"], b["edges"],
                  b["mask"], b["values"], b["labels"], P()),
        out_specs=(P(), (carry_specs, res_specs)),
        check_vma=False)

    @jax.jit
    def step(params, opt_state, carries, comm_res, frames, edges, mask,
             values, labels, t0):
        (loss, (new_carries, new_res)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, carries, comm_res, frames,
                                   edges, mask, values, labels, t0)
        params2, opt2 = adamw.apply_updates(opt_cfg, params, grads,
                                            opt_state)
        return params2, opt2, new_carries, new_res, loss

    return step


def init_sharded_carries(cfg: mdl.DynGNNConfig, params: dict, mesh,
                         axis: str = "data"):
    """Zero carries (full N) placed with their stream shardings."""
    carries = mdl.init_carries(cfg, params)
    shardings = shardlib.named(mesh, shardlib.stream_carry_specs(cfg, axis))
    return jax.tree.map(jax.device_put, carries, shardings)


def init_comm_residuals(cfg: mdl.DynGNNConfig, win: int, mesh,
                        axis: str = "data"):
    """Zero error-feedback residuals for the quantized redistributions,
    placed with their stream shardings: one ``(res_t2n, res_n2t)`` pair
    per layer in the PRE-all-to-all layouts (empty for EvolveGCN)."""
    res = [(jnp.zeros((win, cfg.num_nodes, f1), jnp.float32),
            jnp.zeros((win, cfg.num_nodes, f2), jnp.float32))
           for f1, f2 in partition.a2a_payload_dims(cfg)]
    shardings = shardlib.named(
        mesh, shardlib.stream_comm_residual_specs(cfg, axis))
    return jax.tree.map(jax.device_put, res, shardings)


def lowered_step_hlo(cfg: mdl.DynGNNConfig, mesh, *, win: int,
                     max_edges: int, axis: str = "data",
                     a2a_chunks: int = 1, compression: str = "none",
                     opt_cfg: adamw.AdamWConfig | None = None) -> str:
    """Compiled HLO text of one round step over zero-valued inputs.

    Shared by the structural byte-accounting tests and
    ``benchmarks/scaling_bench.compressed_round`` so both measure the
    SAME lowering (``dist.comm_volume.hlo_collective_bytes`` parses it).
    """
    opt_cfg = opt_cfg or adamw.AdamWConfig(lr=1e-2, warmup_steps=1,
                                           total_steps=1)
    step = make_dist_stream_step(cfg, mesh, opt_cfg, axis,
                                 a2a_chunks=a2a_chunks,
                                 compression=compression)
    # shape-only trace: the key never reaches training
    params = mdl.init_params(jax.random.PRNGKey(0), cfg)  # dynlint: allow[prng]
    opt_state = adamw.init_state(params)
    carries = init_sharded_carries(cfg, params, mesh, axis)
    n = cfg.num_nodes
    args = [params, opt_state, carries]
    if compression != "none":
        args.append(init_comm_residuals(cfg, win, mesh, axis))
    args += [jnp.zeros((win, n, cfg.feat_in)),
             jnp.zeros((win, max_edges, 2), jnp.int32),
             jnp.zeros((win, max_edges)), jnp.zeros((win, max_edges)),
             jnp.zeros((win, n), jnp.int32), jnp.int32(0)]
    return step.lower(*args).compile().as_text()


def dist_round_stream(shard_streams, frames, labels, win: int, bsl: int,
                      start_round: int = 0):
    """Host iterator of one round's payloads: (per-shard delta items,
    frames (win, N, F), labels (win, N)).

    ``start_round`` resumes mid-epoch: the given ``shard_streams`` begin
    at that round's checkpoint-block boundary (see
    ``sharded.encode_time_sliced(start_step=...)``), while frames/labels
    stay globally indexed.
    """
    num_shards = len(shard_streams)
    rounds = len(shard_streams[0]) // bsl
    for r in range(rounds):
        items = tuple(
            tuple(shard_streams[s][r * bsl + j] for j in range(bsl))
            for s in range(num_shards))
        t0 = (start_round + r) * win
        yield (items, np.asarray(frames[t0:t0 + win]),
               np.asarray(labels[t0:t0 + win]))


def make_round_stage_fn(mesh, axis: str = "data"):
    """Round staging for the prefetch thread: each shard's delta items go
    to that shard's device; frames/labels ship with their time-sharded
    ``NamedSharding`` placements directly."""
    devices = shardlib.shard_devices(mesh, axis)
    b = shardlib.stream_batch_specs(axis)
    fr_sh = NamedSharding(mesh, b["frames"])
    lab_sh = NamedSharding(mesh, b["labels"])

    def stage(round_item):
        items, fr, lab = round_item
        staged = tuple(
            tuple(stage_item(it, devices[s]) for it in shard_items)
            for s, shard_items in enumerate(items))
        return staged, jax.device_put(fr, fr_sh), jax.device_put(lab,
                                                                 lab_sh)

    return stage


def consume_round(items, appliers, stackers):
    """Drive one round's staged per-shard delta items through the shard
    rings: ``appliers[s]`` applies shard s's deltas, ``stackers[s]``
    copies each reconstructed slot out of the donated ring.  Returns the
    per-shard ``(edges, mask, values)`` blocks, dispatch-only (nothing
    blocks on device execution).

    This is THE per-round reconstruction protocol — the trainer below
    and the benchmarks that time the transfer phase
    (``benchmarks/overlap_bench.pipelined_round``,
    ``benchmarks/scaling_bench._round_transfer_time``) all call it, so
    the measured phase can never drift from what the trainer overlaps.
    """
    blocks = []
    for s, shard_items in enumerate(items):
        for j, item in enumerate(shard_items):
            e, m, v = appliers[s].consume(item)
            stackers[s].put(j, e, m, v)
        blocks.append(stackers[s].arrays())
    return blocks


def _assemble(mesh, spec, shard_blocks, global_shape):
    """Per-shard device blocks -> one global time-sharded jax.Array
    (zero host round-trip: the blocks already live on their devices)."""
    return jax.make_array_from_single_device_arrays(
        global_shape, NamedSharding(mesh, spec), list(shard_blocks))


def _dist_phase_probe(cfg, opt_cfg, params, opt_state, fr_g, assembled,
                      lab_g, t0) -> tuple[float, float]:
    """One-time comp-reference measurement for derived phase spans.

    The round step is one fused jit, so the spatial / a2a / temporal
    phases cannot be fenced individually inside it.  Mirror the
    methodology of ``benchmarks/overlap_bench.pipelined_round``: compile
    the SAME step on a single-shard mesh (where the two all-to-alls
    degenerate to local copies) and time it on this round's actual data
    — that is the round's communication-free compute reference.  Per
    round, ``a2a = step - comp_ref`` and the remaining compute splits
    between the spatial and temporal stages by their analytic flop
    ratio (the same split the overlap benchmark feeds
    ``round_time_model``).  Returns ``(comp_ref_s, f_spatial)``.
    """
    from repro.launch.mesh import make_host_mesh
    mesh1 = make_host_mesh(data=1)
    step1 = make_dist_stream_step(cfg, mesh1, opt_cfg)
    host = [np.asarray(x) for x in (fr_g, *assembled, lab_g)]
    params_h = jax.tree.map(np.asarray, params)
    opt_h = jax.tree.map(np.asarray, opt_state)
    carries1 = init_sharded_carries(cfg, params_h, mesh1)
    trc = obs.get_tracer()

    def run():
        out = step1(params_h, opt_h, carries1, *host, jnp.int32(t0))
        jax.block_until_ready(out[-1])

    run()                                        # compile + warm
    best = None
    for _ in range(2):
        with trc.stopwatch("round.probe", cat="probe") as sw:
            run()
        best = sw.seconds if best is None else min(best, sw.seconds)
    mask = np.asarray(assembled[1])
    e_mean = float(mask.sum()) / mask.shape[0]
    feat = cfg.hidden
    fl_spatial = 2 * e_mean * 2 * feat + 2 * cfg.num_nodes * feat * feat
    fl_temporal = 2 * cfg.window * cfg.num_nodes * feat * feat
    f_sp = fl_spatial / (fl_spatial + fl_temporal)
    return best, f_sp


def _emit_phase_spans(trc, gr: int, step_span, comp_ref: float,
                      f_sp: float) -> None:
    """Derived spatial/a2a/temporal child spans inside one measured
    ``round.step`` span (marked ``derived`` — see docs/observability.md)."""
    step_s = step_span.dur_s
    a2a_s = max(step_s - comp_ref, 0.0)
    comp_s = step_s - a2a_s
    sp_s = f_sp * comp_s
    t0 = step_span.start_s
    trc.add_span("round.spatial", t0, sp_s, cat="phase.derived",
                 round=gr, derived=True)
    trc.add_span("round.a2a", t0 + sp_s, a2a_s, cat="phase.derived",
                 round=gr, derived=True)
    trc.add_span("round.temporal", t0 + sp_s + a2a_s, comp_s - sp_s,
                 cat="phase.derived", round=gr, derived=True)


def train_distributed_streamed(cfg: mdl.DynGNNConfig, snapshots, values,
                               frames, labels, *, mesh, axis: str = "data",
                               block_size: int | None = None,
                               num_epochs: int = 1, overlap: bool = True,
                               prefetch_depth: int = 2,
                               a2a_chunks: int = 1,
                               pipeline_rounds: bool = False,
                               compression: str = "none",
                               opt_cfg: adamw.AdamWConfig | None = None,
                               params: dict | None = None, opt_state=None,
                               stats: enc.DeltaStats | None = None,
                               max_edges: int | None = None,
                               step_fn=None, shard_streams=None,
                               start_round: int = 0, carries=None,
                               stop_fn=None, seed: int = 0,
                               log_every: int = 10,
                               log_fn=None,
                               step_timer: StepTimer | None = None
                               ) -> DistStreamState:
    """Stream the trace through snapshot-parallel distributed training.

    One round per checkpoint block (``win = block_size`` snapshots): shard
    s receives only its ``win/P`` owned deltas (1/P transfer volume),
    reconstructs them into its slice of the time-sharded block, and the
    round's single train step crosses shards exclusively through the two
    fixed-volume all-to-alls per layer.  ``overlap=True`` stages round
    r+1's per-shard deltas while round r trains; both schedules produce
    identical losses.

    ``a2a_chunks`` / ``pipeline_rounds`` are the chunked-round pipelining
    knobs (see the module docstring): pure schedule changes whose loss
    streams are pinned to the serial (C=1, unpipelined) reference.  With
    ``pipeline_rounds=True`` each shard alternates between two
    ``DeltaApplier`` rings, so round r+1's delta-applies never wait on
    the retirement of buffers round r's assembly still reads, and the
    host forces round r's loss only after round r+1 is fully dispatched.

    ``step_fn`` / ``shard_streams`` let callers that invoke this in a loop
    (benchmark epochs, repeated timing runs) reuse one compiled step and
    one encoded stream set instead of re-tracing and re-encoding per call;
    both must come from ``make_dist_stream_step`` /
    ``sharded.encode_time_sliced`` with matching (cfg, mesh, block,
    a2a_chunks) args.

    ``compression`` ("none" | "int8_a2a" | "int8_all") turns on int8
    error-feedback quantization of the per-layer all-to-alls; "int8_all"
    additionally encodes the per-shard delta streams on the narrow
    ``stream.wire`` format (quantized edge values + int16 indices where
    num_nodes/max_edges allow).  "none" is bit-identical to the
    uncompressed trainer; the compressed loss streams are drift-bounded
    by ``tests/test_compression_drift.py``.  A caller-provided
    ``step_fn``/``shard_streams`` must have been built with the same
    compression mode.

    ``start_round`` / ``carries`` / ``stop_fn`` are the resumable-from-
    block entry the elastic rescale subsystem (``repro.elastic``) drives
    segments through: run the rounds of ONE epoch from checkpoint-block
    boundary ``start_round`` with explicit initial ``carries`` (None =
    fresh zeros, the epoch-start semantics), and stop cleanly at the
    next boundary when ``stop_fn(global_round)`` returns True (SIGTERM,
    scheduled resize).  The final carries ride back on
    ``DistStreamState.carries`` so the caller can re-shard them onto a
    different mesh and continue — these knobs never change the losses of
    the rounds that do run.

    Every round is observed through ``repro.obs`` (one wall-clock
    ``round`` stopwatch per round feeding the ``step_timer`` EWMA
    watchdog — pass one to share it across elastic segments).  When the
    global tracer is enabled the loop additionally records fenced
    ``round.transfer`` / ``round.step`` spans plus the derived
    spatial/a2a/temporal phase spans from the one-time comp-reference
    probe (``_dist_phase_probe``); fencing serializes the schedule, so
    traced runs measure the serial round (docs/observability.md).
    """
    t_steps = len(snapshots)
    num_procs = mesh.shape[axis]
    compression_lib.validate_mode(compression)
    use_comp = compression_lib.compresses_a2a(compression)
    win = block_size or max(t_steps // max(cfg.checkpoint_blocks, 1), 1)
    if win % num_procs:
        raise ValueError(f"block_size {win} must divide into {num_procs} "
                         "shards")
    if t_steps % win:
        raise ValueError(f"trace length {t_steps} must be a multiple of "
                         f"block_size {win}")
    if (start_round or carries is not None) and num_epochs != 1:
        raise ValueError(
            "start_round/carries resume one epoch segment; run with "
            "num_epochs=1 and loop epochs in the caller (repro.elastic)")
    bsl = win // num_procs
    max_edges = max_edges or tl.default_max_edges(snapshots)
    if stats is None and shard_streams is None:
        stats = enc.measure_stats(snapshots, cfg.num_nodes, win, max_edges)
    opt_cfg = opt_cfg or adamw.AdamWConfig(
        lr=1e-2, warmup_steps=10, total_steps=num_epochs * t_steps,
        weight_decay=0.0)
    if params is None:
        params = mdl.init_params(jax.random.PRNGKey(seed), cfg)
    if opt_state is None:
        opt_state = adamw.init_state(params)

    # Per-shard self-contained time-slice streams (encoded once, replayed
    # every epoch): shard s's stream opens each round with a FullSnapshot
    # (slice boundary — it holds nothing to diff against) and deltas after.
    if shard_streams is None:
        shard_streams = stream_sharded.encode_time_sliced(
            snapshots, values, cfg.num_nodes, max_edges, win, num_procs,
            stats, start_step=start_round * win,
            wire=compression_lib.wire_mode(compression))
    per_shard_bytes = [sum(i.payload_bytes for i in s)
                       for s in shard_streams]

    devices = shardlib.shard_devices(mesh, axis)
    b = shardlib.stream_batch_specs(axis)
    if step_fn is None:
        step_fn = make_dist_stream_step(cfg, mesh, opt_cfg, axis,
                                        a2a_chunks=a2a_chunks,
                                        compression=compression)
    stage_fn = make_round_stage_fn(mesh, axis)
    e_pad = max_edges
    # pipeline_rounds double-buffers the per-shard rings: round r uses
    # buffer r%2, so round r+1's delta-applies (and their donations) are
    # fully independent of the ring round r's assembly was built from.
    nbuf = 2 if pipeline_rounds else 1

    def reconstruct_round(r, items, appliers, stackers):
        """Per-shard delta-apply + slot stacking -> assembled global
        (edges, mask, values) for one round, on round r's ring buffer."""
        buf = r % nbuf
        blocks = consume_round(items, [a[buf] for a in appliers],
                               [st[buf] for st in stackers])
        return (_assemble(mesh, b["edges"], (e for e, _, _ in blocks),
                          (win, e_pad, 2)),
                _assemble(mesh, b["mask"], (m for _, m, _ in blocks),
                          (win, e_pad)),
                _assemble(mesh, b["values"], (v for _, _, v in blocks),
                          (win, e_pad)))

    def emit(loss_value):
        losses.append(float(loss_value))
        if log_fn is not None and (len(losses) - 1) % log_every == 0:
            log_fn(f"dist stream round {len(losses) - 1} "
                   f"loss {losses[-1]:.4f} "
                   f"(P={num_procs}, win={win}, C={a2a_chunks}, "
                   f"pipelined={pipeline_rounds})")

    losses: list[float] = []
    initial_carries = carries
    stopped = False
    timer = step_timer if step_timer is not None else StepTimer()
    trc = obs.get_tracer()
    # derived phase spans need fenced (execution-timed) measurements and
    # the comp-reference probe; both are opt-in via the tracer config
    derive_phases = trc.enabled and trc.phases and trc.fencing
    probe: tuple[float, float] | None = None      # (comp_ref_s, f_spatial)
    obs.inc("stream.payload_bytes", sum(per_shard_bytes))
    # span round index: monotonic across epochs (the model-time index
    # ``gr`` deliberately restarts each epoch, which would collide trace
    # rounds and calibration keys)
    ridx = start_round
    for _ in range(num_epochs):
        host = dist_round_stream(shard_streams, frames, labels, win, bsl,
                                 start_round=start_round)
        if overlap:
            rounds = PrefetchIterator(host, stage_fn=stage_fn,
                                      depth=prefetch_depth)
        else:
            rounds = (stage_fn(x) for x in host)
        appliers = [[DeltaApplier(e_pad, device=d) for _ in range(nbuf)]
                    for d in devices]
        stackers = [[SlotStacker(bsl) for _ in range(nbuf)]
                    for _ in devices]
        carries = (initial_carries if initial_carries is not None
                   else init_sharded_carries(cfg, params, mesh, axis))
        initial_carries = None           # later epochs start fresh
        # error-feedback residuals restart at zero with the carries: they
        # are an optimization state of the quantizer, not model state
        comm_res = (init_comm_residuals(cfg, win, mesh, axis)
                    if use_comp else None)
        in_flight = None        # round r-1's device loss (pipeline_rounds)
        try:
            for r, (items, fr_g, lab_g) in enumerate(rounds):
                gr = start_round + r
                with trc.stopwatch("round", cat="round", round=ridx,
                                   p=num_procs, win=win) as round_sw:
                    with trc.span("round.transfer", round=ridx) as tr_sp:
                        assembled = reconstruct_round(r, items, appliers,
                                                      stackers)
                        tr_sp.fence(assembled)
                    with trc.span("round.step", round=ridx) as st_sp:
                        if use_comp:
                            params, opt_state, carries, comm_res, loss = \
                                step_fn(params, opt_state, carries,
                                        comm_res, fr_g, *assembled, lab_g,
                                        jnp.int32(gr * win))
                        else:
                            params, opt_state, carries, loss = step_fn(
                                params, opt_state, carries, fr_g,
                                *assembled, lab_g, jnp.int32(gr * win))
                        st_sp.fence(loss)
                    if pipeline_rounds:
                        # force the PREVIOUS round only now: round r's
                        # delta-applies and step are already dispatched,
                        # so they execute while the host blocks on loss
                        # r-1.
                        if in_flight is not None:
                            emit(in_flight)
                        in_flight = loss
                    else:
                        emit(loss)
                obs.inc("stream.rounds")
                timer.observe(round_sw.seconds)  # counts straggler.flags
                if derive_phases:
                    if probe is None:
                        probe = _dist_phase_probe(
                            cfg, opt_cfg, params, opt_state, fr_g,
                            assembled, lab_g, gr * win)
                    _emit_phase_spans(trc, ridx, st_sp, *probe)
                ridx += 1
                if stop_fn is not None and stop_fn(gr):
                    stopped = True
                    break
            if in_flight is not None:   # drain the pipelined epoch tail
                emit(in_flight)
        finally:
            if isinstance(rounds, PrefetchIterator):
                rounds.close()
        if stopped:
            break
    return DistStreamState(params=params, opt_state=opt_state,
                           losses=losses, per_shard_bytes=per_shard_bytes,
                           carries=carries, step_timer=timer)
