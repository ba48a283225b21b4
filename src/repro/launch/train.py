"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Selects any registered architecture, builds its mesh + train step through
the same cell machinery the dry-run validates, and runs real steps on the
attached devices (host CPU here; a pod in production — the code path is
identical, only the mesh differs).

For the paper's dynamic-GNN archs this drives the full stack (snapshot
partitioning + graph-diff pipeline + checkpointing); for the assigned LM /
GNN / recsys archs it runs their reduced (smoke) configs by default since
the full configs need a pod.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np


def _finish_trace(path: str | None) -> None:
    """Export the session trace (``--trace``)."""
    if not path:
        return
    from repro import obs
    trc = obs.get_tracer()
    out = obs.export_trace(path)
    dropped = f" ({trc.dropped} spans dropped)" if trc.dropped else ""
    print(f"trace: {len(trc.spans())} spans -> {out}{dropped}")


def _parse_rescale(spec: str) -> tuple[int, int]:
    """'BLOCK:P' -> (block, new_p) for the plan's rescale schedule."""
    try:
        block, p = spec.split(":")
        return int(block), int(p)
    except ValueError:
        raise SystemExit(
            f"--rescale-at expects BLOCK:P (e.g. 2:8), got {spec!r}"
        ) from None


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--data-parallel", type=int, default=0,
                    help="0 = all available devices")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--full-config", action="store_true",
                    help="dyngnn: train on the dtdg_epinions shape "
                         "(N=755,200, T=512, ~2.1M smoothed edges per "
                         "snapshot) instead of the smoke config")
    ap.add_argument("--stream", action="store_true",
                    help="dyngnn only: per-snapshot streaming training "
                         "over the async graph-diff delta stream")
    ap.add_argument("--no-overlap", action="store_true",
                    help="with --stream: synchronous reference schedule "
                         "(no prefetch/transfer overlap)")
    ap.add_argument("--epochs", type=int, default=1,
                    help="with --stream: passes over the trace")
    ap.add_argument("--mesh", type=int, default=0,
                    help="with --stream: snapshot-parallel shards; each "
                         "device gets only its own time-slice delta "
                         "stream and blocks train under shard_map "
                         "(0 = single-device streaming)")
    ap.add_argument("--a2a-chunks", type=int, default=1,
                    help="mesh schedules: split each all-to-all "
                         "redistribution into this many feature-sliced "
                         "chunks the scheduler can overlap with compute "
                         "(losses unchanged)")
    ap.add_argument("--pipeline-rounds", action="store_true",
                    help="with --stream --mesh: dispatch round r+1's "
                         "delta-apply/staging before forcing round r's "
                         "loss (double-buffered edge rings; losses "
                         "unchanged)")
    ap.add_argument("--compression", default="none",
                    choices=["none", "int8_a2a", "int8_all"],
                    help="with --stream --mesh: quantized wire formats — "
                         "int8_a2a = error-feedback int8 all-to-alls, "
                         "int8_all = also the narrow host->device delta "
                         "wire (drift-bounded, not bit-exact)")
    ap.add_argument("--rescale-at", action="append", default=[],
                    metavar="BLOCK:P",
                    help="with --stream --mesh: elastically rescale the "
                         "snapshot-parallel width to P at global round "
                         "BLOCK (repeatable; realized at the "
                         "checkpoint-block boundary; losses unchanged)")
    ap.add_argument("--rescale-on-preempt", type=int, default=0,
                    metavar="P",
                    help="with --stream --mesh: absorb SIGTERM by "
                         "shrinking to width P at the next block "
                         "boundary instead of stopping")
    ap.add_argument("--sampled", action="store_true",
                    help="dyngnn only: out-of-core sampled training — "
                         "host-resident temporal store, fanout-sampled "
                         "rounds (docs/sampling.md); combine with --mesh")
    ap.add_argument("--sample-batch", type=int, default=0, metavar="B",
                    help="with --sampled: seed vertices per round "
                         "(default num_nodes // 4)")
    ap.add_argument("--fanout", default="10,10", metavar="K1,K2,...",
                    help="with --sampled: per-hop in-neighbor fanouts")
    ap.add_argument("--device-budget", type=int, default=0, metavar="BYTES",
                    help="dyngnn only: simulated per-device cap on "
                         "round-resident graph tensors; over-budget "
                         "schedules refuse with DeviceBudgetError")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="enable the repro.obs tracer and export a "
                         "Perfetto-loadable Chrome trace of the run "
                         "(spans + counters; .jsonl for one event per "
                         "line)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.trace:
        from repro import obs
        obs.configure(enabled=True)
    if args.sampled and args.stream:
        raise SystemExit("--sampled is its own schedule; drop --stream")
    if (args.sample_batch or args.fanout != "10,10") and not args.sampled:
        # same fail-loudly rule as the rescale flags: a typo'd command
        # must not silently run a different schedule
        raise SystemExit("--sample-batch/--fanout configure the sampled "
                         "schedule; they require --sampled")
    if (args.rescale_at or args.rescale_on_preempt) and not args.stream:
        # fail loudly, never drop the flags: the eager branch has no
        # rescale plumbing, so a typo'd command would otherwise run a
        # plain fixed-width schedule without a word
        raise SystemExit("--rescale-at/--rescale-on-preempt recompose the "
                         "distributed stream; they require "
                         "--stream --mesh P")

    from repro.configs import registry
    from repro.launch.mesh import make_host_mesh

    arch = registry.get_arch(args.arch)
    n_dev = len(jax.devices())
    dp = args.data_parallel or max(d for d in (1, 2, 4, 8, 16) if
                                   d <= n_dev)

    if arch.family == "dyngnn":
        from repro.run import (CheckpointSpec, DeviceBudgetError, Engine,
                               ExecutionPlan, RunConfig, SamplingSpec,
                               SyntheticTrace)
        from repro.configs import paper_dyngnn
        if args.full_config:
            cfg = arch.make_config()
            data = paper_dyngnn.synthetic_trace(cfg)
        else:
            cfg = arch.make_smoke_config()
            data = SyntheticTrace(
                num_nodes=cfg.num_nodes, num_steps=cfg.num_steps,
                density=3.0, churn=paper_dyngnn.CHURN,
                smoothing_mode=paper_dyngnn.SMOOTHING[cfg.model],
                window=cfg.window)
        budget = args.device_budget or None
        if args.sampled:
            try:
                fanouts = tuple(int(k) for k in args.fanout.split(","))
            except ValueError:
                raise SystemExit(f"bad --fanout {args.fanout!r}; expected "
                                 "K1,K2,...") from None
            spec = SamplingSpec(
                batch_nodes=args.sample_batch or max(cfg.num_nodes // 4, 1),
                fanouts=fanouts)
            plan = ExecutionPlan(mode="sampled", shards=max(args.mesh, 1),
                                 num_epochs=args.epochs,
                                 overlap=not args.no_overlap,
                                 a2a_chunks=args.a2a_chunks,
                                 compression=args.compression,
                                 sampling=spec, device_budget_bytes=budget)
            ckpt = None
            if args.ckpt_dir:
                print("note: --ckpt-dir is ignored with --sampled "
                      "(checkpointing is wired for the eager and "
                      "streamed --mesh schedules)")
        elif args.stream:
            # non-divisible num_nodes auto-pads inside the plan (logged);
            # the pipelining/rescale flags pass through VERBATIM so a
            # combination the plan cannot honor (e.g. --a2a-chunks or
            # --rescale-at without --mesh) fails loudly below instead of
            # silently running a no-op
            plan = ExecutionPlan(
                mode="streamed_mesh" if args.mesh > 1 else "streamed",
                shards=max(args.mesh, 1), num_epochs=args.epochs,
                overlap=not args.no_overlap,
                a2a_chunks=args.a2a_chunks,
                pipeline_rounds=args.pipeline_rounds,
                compression=args.compression,
                rescale=tuple(_parse_rescale(s) for s in args.rescale_at),
                rescale_on_preempt=args.rescale_on_preempt,
                device_budget_bytes=budget)
            ckpt = None
            if args.ckpt_dir:
                if plan.mode == "streamed_mesh":
                    # round-granular mesh-agnostic checkpoints: SIGTERM
                    # saves the data cursor; a rerun resumes it, on any
                    # legal --mesh width
                    ckpt = CheckpointSpec(args.ckpt_dir)
                else:
                    print("note: --ckpt-dir is ignored with single-device "
                          "--stream (checkpointing is wired for the eager "
                          "and streamed --mesh schedules)")
        else:
            plan = ExecutionPlan(mode="eager", shards=dp,
                                 num_steps=args.steps,
                                 a2a_chunks=args.a2a_chunks,
                                 pipeline_rounds=args.pipeline_rounds,
                                 compression=args.compression,
                                 device_budget_bytes=budget)
            ckpt = (CheckpointSpec(args.ckpt_dir)
                    if args.ckpt_dir else None)
        try:
            # surface plan/config contradictions (e.g. a trace length the
            # shards cannot slice, a bad --a2a-chunks) as a one-line CLI
            # error, not a traceback
            engine = Engine(RunConfig(model=cfg, data=data, plan=plan,
                                      checkpoint=ckpt))
            engine.resolve()
        except ValueError as e:
            raise SystemExit(f"invalid run configuration: {e}") from None
        try:
            result = engine.fit()
        except DeviceBudgetError as e:
            # the budget gate refusing IS the answer the flag asks for —
            # report it as a one-line CLI outcome, not a traceback
            raise SystemExit(f"refused: {e}") from None
        _finish_trace(args.trace)
        rep = result.transfer_report
        if args.sampled:
            final = (f"{result.losses[-1]:.4f}" if result.losses else "n/a")
            srep = result.sample_report
            budget_txt = (f", budget {result.budget_report['required']}"
                          f"/{result.budget_report['budget']} B"
                          if result.budget_report else "")
            print(f"sampled {srep.rounds} rounds on "
                  f"{max(args.mesh, 1)} shards, final loss {final}, "
                  f"staged {srep.staged_bytes} B, sampled edges "
                  f"{srep.sampled_edges} (dropped {srep.dropped_edges} "
                  f"edges / {srep.dropped_nodes} nodes){budget_txt}")
            return
        if args.stream:
            final = (f"{result.losses[-1]:.4f}" if result.losses else "n/a")
            if plan.mode == "streamed_mesh":
                rsc = result.rescale_report
                if rsc is not None and (rsc.events or rsc.preempted
                                        or rsc.resumed_from is not None):
                    # elastic summary: the width trajectory, not a single
                    # per-device figure (each segment has its own P)
                    evs = ", ".join(
                        f"{e.old_p}->{e.new_p}@block{e.block}"
                        f" ({e.cause}, {e.payload_bytes} B)"
                        for e in rsc.events) or "none realized"
                    if not rsc.preempted:
                        state_txt = "completed"
                    elif ckpt is not None:
                        state_txt = "preempted+checkpointed"
                    else:       # no --ckpt-dir: progress was NOT saved
                        state_txt = "preempted (no checkpoint configured)"
                    print(f"streamed {result.state.step} block rounds "
                          f"elastically ({state_txt}), final loss "
                          f"{final}, rescales: {evs}")
                    return
                # report what actually crossed the links: the per-shard
                # time-sliced streams (extra slice-boundary fulls), not
                # the single-device global stream
                per_dev = result.per_shard_bytes
                comp_txt = (f", compression {result.compression}"
                            if result.compression != "none" else "")
                print(f"streamed {result.state.step} block rounds on "
                      f"{args.mesh} shards, final loss {final}, "
                      f"per-device stream {max(per_dev)} B (total "
                      f"{sum(per_dev) / max(rep['naive'], 1):.3f} of "
                      f"naive){comp_txt}")
            else:
                print(f"streamed {result.state.step} snapshot steps, "
                      f"final loss {final}, transfer ratio "
                      f"{rep['ratio']:.3f} vs naive")
            return
        acc = engine.evaluate(result)
        # a checkpoint resume at/past --steps trains zero new steps
        final = f"{result.losses[-1]:.4f}" if result.losses else "n/a"
        print(f"done: {result.state.step} steps, final loss "
              f"{final}, link-pred acc {acc:.3f}")
        return

    # LM / GNN / recsys: drive one cell's train step repeatedly
    from repro.launch import steps as steps_mod
    mesh = make_host_mesh(data=dp, model=max(n_dev // dp, 1))
    shape_name = {"lm": "train_4k", "gnn": "molecule",
                  "recsys": "train_batch"}[arch.family]
    override = {"lm": {"seq_len": 128, "global_batch": 2 * dp},
                "gnn": {"n_nodes": 16, "n_edges": 32, "batch": 2 * dp,
                        "d_feat": 8, "num_classes": 2},
                "recsys": {"batch": 16 * dp}}[arch.family]
    cell = steps_mod.build_cell(args.arch, shape_name, mesh,
                                smoke=not args.full_config,
                                shape_override=None if args.full_config
                                else override)
    rng = np.random.default_rng(0)
    import jax.numpy as jnp

    def concretize(a):
        if a.dtype in (jnp.int32, jnp.int64):
            return jnp.asarray(rng.integers(0, 2, a.shape), a.dtype)
        return jnp.asarray(rng.normal(0, 0.1, a.shape), a.dtype)

    args_c = list(jax.tree.map(concretize, cell.abstract_inputs))
    with mesh:
        step = jax.jit(cell.step, in_shardings=cell.in_shardings,
                       out_shardings=cell.out_shardings)
        for i in range(args.steps):
            out = step(*args_c)
            params, opt_state, loss = out
            args_c[0], args_c[1] = params, opt_state
            if i % max(args.steps // 10, 1) == 0:
                print(f"step {i} loss {float(loss):.4f}")
    _finish_trace(args.trace)
    print("done")


if __name__ == "__main__":
    main()
