"""Private training workers behind ``Engine.fit()``.

Each worker consumes one :class:`repro.run.config.ResolvedRun` bundle
and drives the corresponding training loop:

* ``fit_eager``         — the blocked offline trainer (single-device or
  snapshot-partition shard_map), with async checkpointing, preemption
  guard, and straggler timing — the loop that used to live inside
  ``trainer.train_dyngnn``;
* ``fit_streamed``      — per-snapshot online training over the
  graph-diff delta stream (``repro.stream.train_loop``);
* ``fit_streamed_mesh`` — per-shard delta streams + snapshot-parallel
  shard_map (``repro.stream.distributed``); when the plan is elastic
  (``rescale`` / ``rescale_on_preempt``) or a checkpoint is configured
  it routes through ``repro.elastic.train_elastic_streamed`` — the
  segment loop that can change the snapshot-parallel width at
  checkpoint-block boundaries and checkpoint/resume the data cursor;
* ``fit_sampled``       — out-of-core sampled training
  (``repro.hoststore``): the trace stays host-resident in a
  ``TemporalCSRStore`` and only fanout-sampled subgraph tensors stream
  to the mesh.

Every worker first gates against ``plan.device_budget_bytes``
(``_budget_gate``) BEFORE allocating device graph tensors: full-graph
schedules refuse a graph whose resident tensors exceed the budget
(``DeviceBudgetError`` names the sampled schedule as the way out).

These are the ONLY call sites of the stream training loops outside the
deprecation shims; everything user-facing goes through the Engine.
Compiled steps and encoded shard streams are cached on the bundle so
repeated ``fit()`` calls (benchmark epochs, resume) reuse them.
"""

from __future__ import annotations

import jax
import numpy as np

from repro.ckpt.checkpoint import Checkpointer
from repro.core import models as dyn_models
from repro.dist import compression as compression_lib
from repro.ft.elastic import PreemptionGuard
from repro.ft.straggler import StepTimer
from repro import hoststore
from repro.hoststore import budget as hostbudget
from repro.optim import adamw
from repro.run.config import ResolvedRun, RunResult
from repro.stream import distributed as stream_dist
from repro.stream import encoder as stream_enc
from repro.stream import train_loop as stream_train
from repro.train import trainer


def _init(rr: ResolvedRun):
    params = dyn_models.init_params(jax.random.PRNGKey(rr.seed), rr.cfg)
    return params, adamw.init_state(params)


def _budget_gate(rr: ResolvedRun, resolved=None) -> dict | None:
    """Gate the schedule against ``plan.device_budget_bytes`` BEFORE any
    device graph tensor is allocated (raises ``DeviceBudgetError`` when
    the resident graph tensors do not fit)."""
    plan = rr.plan
    return hostbudget.check_budget(
        plan.mode, plan.device_budget_bytes,
        num_steps=rr.ds.num_steps, win=rr.pipeline.bsize,
        num_shards=plan.num_shards, max_edges=rr.pipeline.max_edges,
        num_nodes=rr.ds.num_nodes,
        feat_dim=rr.ds.frames.shape[-1], resolved=resolved)


def fit_eager(rr: ResolvedRun) -> RunResult:
    plan = rr.plan
    budget = _budget_gate(rr)
    num_steps = plan.num_steps
    opt_cfg = rr.opt_cfg or adamw.AdamWConfig(
        lr=1e-2, warmup_steps=10, total_steps=num_steps, weight_decay=0.0)
    params, opt_state = _init(rr)
    start_step = 0
    ckpt = Checkpointer(rr.checkpoint.directory) if rr.checkpoint else None
    if ckpt and ckpt.latest_step() is not None:
        s = ckpt.latest_step()
        (params, opt_state), extra = ckpt.restore(s, (params, opt_state))
        start_step = extra.get("train_step", s)
        rr.log_fn(f"resumed from checkpoint step {start_step}")

    frames, edges, ew, labels = rr.pipeline.blocked_arrays()
    step_fn = rr.cache.get("eager_step")
    if rr.mesh is not None:
        if step_fn is None:
            step_fn = trainer.make_dyngnn_train_step(
                rr.cfg, rr.mesh, opt_cfg, a2a_chunks=plan.a2a_chunks)
            rr.cache["eager_step"] = step_fn
        args = (frames, edges, ew, labels)
    else:
        if step_fn is None:
            step_fn = trainer.make_single_device_train_step(rr.cfg, opt_cfg)
            rr.cache["eager_step"] = step_fn
        lab = labels.reshape((-1,) + labels.shape[2:])
        args = (rr.pipeline.batch, lab)

    timer = StepTimer()
    losses: list[float] = []
    with PreemptionGuard() as guard:
        for step in range(start_step, num_steps):
            with timer:
                params, opt_state, loss = step_fn(params, opt_state, *args)
            losses.append(float(loss))
            if step % rr.log_every == 0:
                rr.log_fn(f"step {step} loss {float(loss):.4f}")
            if ckpt and (step + 1) % rr.checkpoint.every == 0:
                ckpt.save(step + 1, (params, opt_state),
                          extra={"train_step": step + 1})
            if guard.preempted:
                rr.log_fn(f"preempted at step {step}; checkpointing and "
                          "exiting cleanly")
                if ckpt:
                    ckpt.save(step + 1, (params, opt_state),
                              extra={"train_step": step + 1},
                              blocking=True)
                break
    if ckpt:
        ckpt.wait()
    state = trainer.TrainState(
        params=params, opt_state=opt_state,
        step=min(num_steps, start_step + len(losses)))
    return RunResult(state=state, losses=losses,
                     transfer_report=rr.pipeline.transfer_bytes(),
                     a2a_chunks=plan.a2a_chunks, budget_report=budget)


def fit_streamed(rr: ResolvedRun) -> RunResult:
    plan, ds, pipe = rr.plan, rr.ds, rr.pipeline
    budget = _budget_gate(rr)
    opt_cfg = rr.opt_cfg or adamw.AdamWConfig(
        lr=1e-2, warmup_steps=10,
        total_steps=plan.num_epochs * ds.num_steps, weight_decay=0.0)
    params, opt_state = _init(rr)
    step_fn = rr.cache.get("stream_step")
    if step_fn is None:
        step_fn = stream_train.make_stream_train_step(rr.cfg, opt_cfg)
        rr.cache["stream_step"] = step_fn
    report = stream_enc.StreamReport()
    st = stream_train.train_streamed(
        rr.cfg, ds.snapshots, ds.values, np.asarray(ds.frames),
        np.asarray(ds.labels), block_size=pipe.bsize,
        num_epochs=plan.num_epochs, overlap=plan.overlap,
        prefetch_depth=plan.prefetch_depth, opt_cfg=opt_cfg,
        params=params, opt_state=opt_state, stats=pipe.stream_stats,
        max_edges=pipe.max_edges, report=report,
        encoded=pipe.stream_items, step_fn=step_fn,
        log_every=rr.log_every, log_fn=rr.log_fn)
    state = trainer.TrainState(params=st.params, opt_state=st.opt_state,
                               step=len(st.losses))
    return RunResult(state=state, losses=st.losses, stream_report=report,
                     transfer_report=pipe.transfer_bytes(),
                     budget_report=budget)


def fit_streamed_mesh(rr: ResolvedRun) -> RunResult:
    plan, ds, pipe = rr.plan, rr.ds, rr.pipeline
    budget = _budget_gate(rr)
    opt_cfg = rr.opt_cfg or adamw.AdamWConfig(
        lr=1e-2, warmup_steps=10,
        total_steps=plan.num_epochs * ds.num_steps, weight_decay=0.0)
    if plan.is_elastic or rr.checkpoint is not None:
        return _fit_streamed_mesh_elastic(rr, opt_cfg, budget)
    params, opt_state = _init(rr)
    step_fn = rr.cache.get("dist_step")
    if step_fn is None:
        step_fn = stream_dist.make_dist_stream_step(
            rr.cfg, rr.mesh, opt_cfg, plan.mesh_axis,
            a2a_chunks=plan.a2a_chunks, compression=plan.compression)
        rr.cache["dist_step"] = step_fn
    shard_streams = rr.cache.get("shard_streams")
    if shard_streams is None:
        shard_streams = pipe.sharded_streams(
            plan.num_shards, wire=compression_lib.wire_mode(plan.compression))
        rr.cache["shard_streams"] = shard_streams
    st = stream_dist.train_distributed_streamed(
        rr.cfg, ds.snapshots, ds.values, np.asarray(ds.frames),
        np.asarray(ds.labels), mesh=rr.mesh, axis=plan.mesh_axis,
        block_size=pipe.bsize, num_epochs=plan.num_epochs,
        overlap=plan.overlap, prefetch_depth=plan.prefetch_depth,
        a2a_chunks=plan.a2a_chunks, pipeline_rounds=plan.pipeline_rounds,
        compression=plan.compression,
        opt_cfg=opt_cfg, params=params, opt_state=opt_state,
        stats=pipe.stream_stats, max_edges=pipe.max_edges,
        step_fn=step_fn, shard_streams=shard_streams,
        log_every=rr.log_every, log_fn=rr.log_fn)
    state = trainer.TrainState(params=st.params, opt_state=st.opt_state,
                               step=len(st.losses))
    return RunResult(state=state, losses=st.losses,
                     transfer_report=pipe.transfer_bytes(),
                     per_shard_bytes=st.per_shard_bytes,
                     a2a_chunks=plan.a2a_chunks,
                     pipeline_rounds=plan.pipeline_rounds,
                     compression=plan.compression,
                     budget_report=budget)


def _fit_streamed_mesh_elastic(rr: ResolvedRun, opt_cfg: adamw.AdamWConfig,
                               budget: dict | None = None) -> RunResult:
    """Elastic / checkpointed variant of the streamed_mesh schedule.

    Same round protocol, driven in constant-width segments by
    ``repro.elastic.train_elastic_streamed``: scripted rescales and
    SIGTERM shrinks recompose the stream at block boundaries, and a
    configured ``CheckpointSpec`` enables round-granular save + resume
    (onto any legal width — the checkpoint is mesh-agnostic).
    """
    from repro import elastic as el

    plan, ds, pipe = rr.plan, rr.ds, rr.pipeline
    params, opt_state = _init(rr)
    rt = rr.cache.get("elastic_runtime")
    if rt is None or rt.a2a_chunks != plan.a2a_chunks:
        rt = el.ElasticRuntime(rr.cfg, opt_cfg, plan.mesh_axis,
                               a2a_chunks=plan.a2a_chunks)
        rt.meshes.setdefault(plan.num_shards, rr.mesh)
        rr.cache["elastic_runtime"] = rt

    ckpt = Checkpointer(rr.checkpoint.directory) if rr.checkpoint else None
    rpe = ds.num_steps // pipe.bsize
    start, carries = 0, None
    if ckpt is not None and ckpt.latest_step() is not None:
        like = {"params": params, "opt": opt_state,
                "carries": dyn_models.init_carries(rr.cfg, params)}
        tree, extra = ckpt.restore(ckpt.latest_step(), like)
        start = int(extra.get("cursor", 0))
        saved_rpe = int(extra.get("rounds_per_epoch", rpe))
        if saved_rpe != rpe:
            # the cursor counts rounds of the ORIGINAL block size; under
            # a plan that re-blocks the timeline it would land mid-block
            # and silently skip (or repeat) snapshots
            raise ValueError(
                f"checkpoint under {rr.checkpoint.directory} was written "
                f"with {saved_rpe} rounds per epoch but this plan blocks "
                f"the timeline into {rpe}; resume with a shard width that "
                "preserves the checkpoint block size")
        params, opt_state = tree["params"], tree["opt"]
        # carries only matter mid-epoch; at an epoch boundary the loop
        # re-initializes them (the uninterrupted-run semantics)
        carries = tree["carries"] if start % rpe else None
        rr.log_fn(f"resumed streamed_mesh run at round {start} "
                  f"(checkpoint written at P={extra.get('p', '?')}, "
                  f"resuming on P={plan.num_shards})")

    # scripted boundaries BEFORE the resume cursor are history — realized
    # (and recorded) by the run that wrote the checkpoint; replaying them
    # would double-count the payload.  A boundary AT the cursor is still
    # pending: events realize at the top of the iteration for their
    # block, and checkpoints are written with cursor == segment end,
    # i.e. before that iteration ran.
    schedule = tuple((b, p) for b, p in plan.rescale if int(b) >= start)
    with PreemptionGuard() as guard:
        controller = el.RescaleController(
            initial_p=plan.num_shards, schedule=schedule, guard=guard,
            shrink_to=plan.rescale_on_preempt or None)
        st = el.train_elastic_streamed(
            rr.cfg, ds.snapshots, ds.values, np.asarray(ds.frames),
            np.asarray(ds.labels), controller=controller,
            axis=plan.mesh_axis, block_size=pipe.bsize,
            num_epochs=plan.num_epochs, overlap=plan.overlap,
            prefetch_depth=plan.prefetch_depth,
            a2a_chunks=plan.a2a_chunks,
            pipeline_rounds=plan.pipeline_rounds, opt_cfg=opt_cfg,
            params=params, opt_state=opt_state, stats=pipe.stream_stats,
            max_edges=pipe.max_edges, runtime=rt, ckpt=ckpt,
            ckpt_every=(rr.checkpoint.every if rr.checkpoint else 0),
            start_cursor=start, carries=carries, log_every=rr.log_every,
            log_fn=rr.log_fn)

    # a COMPLETED run that never changed width has one well-defined
    # per-shard byte accounting: the first epoch's segments sum to
    # exactly the encoded stream (epochs replay the same streams, so —
    # like the fixed-width path — the stream is counted once, not per
    # epoch).  Rescaled, resumed, or preempted runs report per-segment
    # PLANNED payloads on the rescale_report instead (a preempted
    # segment's tail never actually streamed).
    per_shard = None
    if (st.completed and not st.report.events
            and st.report.resumed_from is None and st.report.segments):
        first_epoch = [seg for seg in st.report.segments if seg[0] < rpe]
        per_shard = [sum(seg[2][s] for seg in first_epoch)
                     for s in range(len(first_epoch[0][2]))]
    state = trainer.TrainState(params=st.params, opt_state=st.opt_state,
                               step=st.cursor)
    return RunResult(state=state, losses=st.losses,
                     transfer_report=pipe.transfer_bytes(),
                     per_shard_bytes=per_shard,
                     a2a_chunks=plan.a2a_chunks,
                     pipeline_rounds=plan.pipeline_rounds,
                     rescale_report=st.report,
                     budget_report=budget)


def fit_sampled(rr: ResolvedRun) -> RunResult:
    """Out-of-core sampled schedule: host-resident store + fanout-sampled
    subgraph streaming (``repro.hoststore.train_sampled``)."""
    plan, ds, pipe = rr.plan, rr.ds, rr.pipeline
    spec = plan.sampling
    resolved = spec.resolve(ds.num_nodes, pipe.bsize, plan.num_shards)
    budget = _budget_gate(rr, resolved)
    opt_cfg = rr.opt_cfg or adamw.AdamWConfig(
        lr=1e-2, warmup_steps=10,
        total_steps=plan.num_epochs * ds.num_steps, weight_decay=0.0)
    params, opt_state = _init(rr)
    store = rr.cache.get("host_store")
    if store is None:
        # SAME delta items as the device path: the store ingests the
        # pipeline's IncrementalEncoder stream, no second decode
        store = hoststore.TemporalCSRStore.from_stream(
            pipe.host_stream(), ds.num_nodes)
        rr.cache["host_store"] = store
    step_fn = rr.cache.get("sampled_step")
    if step_fn is None:
        step_fn = hoststore.make_sampled_step(
            rr.cfg, resolved, rr.mesh, opt_cfg, plan.mesh_axis,
            a2a_chunks=plan.a2a_chunks)
        rr.cache["sampled_step"] = step_fn
    st = hoststore.train_sampled(
        rr.cfg, store, np.asarray(ds.frames), np.asarray(ds.labels),
        spec=spec, mesh=rr.mesh, axis=plan.mesh_axis,
        block_size=pipe.bsize, num_epochs=plan.num_epochs,
        overlap=plan.overlap, prefetch_depth=plan.prefetch_depth,
        a2a_chunks=plan.a2a_chunks, opt_cfg=opt_cfg, params=params,
        opt_state=opt_state, step_fn=step_fn, seed=rr.seed,
        log_every=rr.log_every, log_fn=rr.log_fn)
    state = trainer.TrainState(params=st.params, opt_state=st.opt_state,
                               step=len(st.losses))
    return RunResult(state=state, losses=st.losses,
                     transfer_report=pipe.transfer_bytes(),
                     a2a_chunks=plan.a2a_chunks,
                     sample_report=st.report, budget_report=budget)
