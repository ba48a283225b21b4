#!/usr/bin/env python3
"""Smoke run of the main training path on TPU, at the paper's model width
and the ``dtdg_epinions`` graph size (N=755,200, ~2.1M smoothed edges per
snapshot).  Only the trace length is cut: T=512 -> 32 snapshots.

    python chip_smoke.py             # one chip: kernels, train, serve
    python chip_smoke.py --chips 4   # four chips: streamed_mesh P=4 vs P=1

One process drives every phase, and it runs only where JAX finds a TPU.
Each phase checks its result and raises on a failure; the last line of
standard output, ``{"ok": true, "device": {...}}``, is printed only after
every phase has passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

# The reference losses are recomputed on the host CPU backend: keep it
# available next to the TPU where the environment names the platforms.
_PLATFORMS = os.environ.get("JAX_PLATFORMS", "")
if _PLATFORMS and "cpu" not in _PLATFORMS.split(","):
    os.environ["JAX_PLATFORMS"] = _PLATFORMS + ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

SEED = 0
T_CUT = 32              # snapshots kept of the shape's T=512 (4 blocks of 8)
EPOCHS = 2
MESH_EPOCHS = 1         # --chips 4: P=4 and P=1 each train one epoch
STEADY_STEPS = 10       # timed repeats of the per-snapshot step
# TPU f32 matmuls run at default (bf16-pass) precision, so the CPU suite's
# 1e-5 pins do not carry over; the chip must still agree with the host
# CPU backend on the same losses to 1e-3 relative.
LOSS_RTOL = 1e-3
# The kernels run at HIGHEST precision and are held to an f64 reference.
KERNEL_ATOL = 1e-5
# Served scores vs the offline forward: both on the chip, same math in a
# different program (per-window steps vs one blocked scan).
SERVE_ATOL = 1e-4
# kernel phase: segment_spmm pads F to 128 lanes, so it runs on a graph
# cut to 65,536 vertices at the trace's ~2.78 edges per vertex
SPMM_NODES = 65_536
SPMM_EDGES_PER_NODE = 2.78
SPMM_EDGES_PER_BLOCK = 4096
SERVE_WINDOWS, SERVE_BLOCK, SERVE_EVENTS = 4, 2, 300_000


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit is counted with its read time)."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


# ------------------------------------------------------------- phases -------

def peak_bytes(dev) -> int:
    return dev.memory_stats()["peak_bytes_in_use"]


def device_phase(chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU: JAX's default backend is "
                         f"{devs[0].platform!r}; refusing to run elsewhere")
    check(len(devs) >= chips, f"{chips} chips requested, JAX sees "
          f"{len(devs)}")
    log(f"device: jax {jax.__version__}, {devs[0].device_kind}, "
        f"{len(devs)} device(s), JAX_PLATFORMS={_PLATFORMS or '<unset>'}")
    from repro.kernels.common import resolve_interpret
    check(resolve_interpret(None) is False,
          "Pallas kernels would run in interpret mode on this backend")
    return devs


def kernel_phase(n_nodes: int, cfg) -> None:
    """Both Pallas kernels compiled for the chip, against f64 numpy."""
    from repro.kernels.common import resolve_interpret
    from repro.kernels.mproduct.mproduct import banded_ttm
    from repro.kernels.mproduct.ref import m_matrix
    from repro.kernels.segment_spmm import ops as spmm_ops

    interpret = resolve_interpret(None)     # False: checked by device_phase

    rng = np.random.default_rng(SEED)
    t_blk = T_CUT // cfg.checkpoint_blocks
    x = rng.standard_normal((t_blk, n_nodes * cfg.hidden), dtype=np.float32)
    ttm = jax.jit(lambda v: banded_ttm(v, cfg.window, 0,
                                         interpret=interpret))
    mosaic = "tpu_custom_call" in ttm.lower(x).as_text()
    got = np.asarray(ttm(x))
    want = m_matrix(t_blk, cfg.window).astype(np.float64) @ x
    err = float(np.max(np.abs(got - want)))
    log(f"kernel banded_ttm: T={t_blk} NF={n_nodes}*{cfg.hidden}, "
        f"Mosaic kernel={mosaic}, max abs err vs f64 {err!r}")
    check(mosaic and err <= KERNEL_ATOL, f"banded_ttm err {err}")

    n = SPMM_NODES
    e = int(n * SPMM_EDGES_PER_NODE)
    edges = rng.integers(0, n, size=(e, 2)).astype(np.int32)
    w = rng.standard_normal(e).astype(np.float32)
    feats = rng.standard_normal((n, cfg.hidden)).astype(np.float32)
    over = int(spmm_ops.bucket_overflow_count(
        edges, w, n, jnp.int32(SPMM_EDGES_PER_BLOCK)))
    spmm = jax.jit(lambda a, b, c: spmm_ops.segment_spmm(
        a, b, c, n, node_block=128, feat_block=128,
        edges_per_block=SPMM_EDGES_PER_BLOCK, interpret=interpret))
    mosaic = "tpu_custom_call" in spmm.lower(feats, edges, w).as_text()
    got = np.asarray(spmm(feats, edges, w))
    want = np.zeros((n, cfg.hidden))
    np.add.at(want, edges[:, 1], feats[edges[:, 0]].astype(np.float64)
              * w[:, None])
    err = float(np.max(np.abs(got - want)))
    log(f"kernel segment_spmm: N={n} E={e} F={cfg.hidden} node_block=128 "
        f"feat_block=128 edges_per_block={SPMM_EDGES_PER_BLOCK}, "
        f"overflow={over}, Mosaic kernel={mosaic}, max abs err vs f64 "
        f"{err!r}")
    check(mosaic and over == 0 and err <= KERNEL_ATOL,
          f"segment_spmm err {err}, overflow {over}")


def train_phase(cfg, data, opt, clock: CompileClock):
    """Engine.fit() on the streamed schedule, then its checks."""
    from repro.core import models as mdl
    from repro.run import Engine, ExecutionPlan, RunConfig
    from repro.stream.prefetch import DeltaApplier, stage_item

    eng = Engine(RunConfig(model=cfg, data=data,
                           plan=ExecutionPlan(mode="streamed",
                                              num_epochs=EPOCHS),
                           optimizer=opt, seed=SEED, log_every=8,
                           log_fn=log))
    t0 = time.perf_counter()
    rr = eng.resolve()
    setup_s = time.perf_counter() - t0
    ds, pipe = rr.ds, rr.pipeline
    n_edges = [s.shape[0] for s in ds.snapshots]
    log(f"train: {cfg.model} N={ds.num_nodes} T={ds.num_steps} (cut from "
        f"the shape's 512) in {rr.cfg.checkpoint_blocks} blocks of "
        f"{pipe.bsize}; smoothed edges/snapshot mean {float(np.mean(n_edges))!r} "
        f"max {max(n_edges)}; edge lanes {pipe.max_edges}")
    log(f"train: host setup (generate, smooth, encode) {setup_s!r} s")

    c0 = clock.seconds
    t0 = time.perf_counter()
    res = eng.fit()
    fit_s = time.perf_counter() - t0
    fit_compile_s = clock.seconds - c0

    # steady per-snapshot step time: the fit's own compiled step, re-run
    # on one reconstructed snapshot, each call ended by block_until_ready
    step = rr.cache["stream_step"]
    edges, mask, vals = DeltaApplier(pipe.max_edges).consume(
        stage_item(next(iter(pipe.host_stream()))))
    frame, lab = jax.device_put(ds.frames[0]), jax.device_put(ds.labels[0])
    params, opt_state = res.state.params, res.state.opt_state
    carries = mdl.init_carries(rr.cfg, params)
    args = (params, opt_state, carries, frame, edges, mask, vals, lab,
            jnp.int32(0))
    jax.block_until_ready(step(*args))
    times = []
    for _ in range(STEADY_STEPS):
        t0 = time.perf_counter()
        jax.block_until_ready(step(*args))
        times.append(time.perf_counter() - t0)
    peak = peak_bytes(jax.devices()[0])
    losses = np.asarray(res.losses)
    per_epoch = losses.reshape(EPOCHS, -1).mean(axis=1)
    log(f"train: fit {fit_s!r} s for {losses.size} snapshot steps, of "
        f"which backend compile {fit_compile_s!r} s; steady step "
        f"median {float(np.median(times))!r} s min {min(times)!r} s")
    log(f"train: losses {losses.tolist()}")
    log(f"train: epoch mean losses {per_epoch.tolist()}")
    log(f"train: graph-diff transfer ratio "
        f"{res.transfer_report['ratio']!r} "
        f"({res.transfer_report['graph_diff']} of "
        f"{res.transfer_report['naive']} B)")
    log(f"train: peak HBM {peak} B ({peak / 2**30:.3f} GiB)")
    check(bool(np.all(np.isfinite(losses))), "non-finite loss")
    check(per_epoch[-1] < per_epoch[0], "epoch-2 mean loss did not fall")
    return rr, res


def cpu_reference_phase(rr, res, opt) -> None:
    """The first block's per-snapshot losses recomputed on the host CPU
    backend from the same initial params."""
    from repro.core import models as mdl
    from repro.optim import adamw
    from repro.stream.train_loop import train_streamed

    cpu = jax.devices("cpu")[0]
    ds, pipe, bs = rr.ds, rr.pipeline, rr.pipeline.bsize
    params0 = jax.device_put(
        mdl.init_params(jax.random.PRNGKey(rr.seed), rr.cfg), cpu)
    t0 = time.perf_counter()
    with jax.default_device(cpu):
        ref = train_streamed(
            rr.cfg, ds.snapshots[:bs], ds.values[:bs], ds.frames[:bs],
            ds.labels[:bs], block_size=bs, num_epochs=1, overlap=False,
            opt_cfg=opt, params=params0, opt_state=adamw.init_state(params0),
            stats=pipe.stream_stats, max_edges=pipe.max_edges)
    chip = np.asarray(res.losses[:bs])
    host = np.asarray(ref.losses)
    gap = float(np.max(np.abs(chip - host) / np.abs(host)))
    log(f"reference: first block on the host CPU ({time.perf_counter() - t0!r}"
        f" s) {host.tolist()}")
    log(f"reference: max relative loss gap chip vs CPU {gap!r} "
        f"(tolerance {LOSS_RTOL})")
    check(gap <= LOSS_RTOL, f"chip/CPU loss gap {gap} > {LOSS_RTOL}")


def serve_phase(rr, res) -> None:
    """Trained params served online at the same N, against the offline
    forward on the chip."""
    from repro.core import checkpoint as ckpt
    from repro.core import ctdg
    from repro.core import models as mdl
    from repro.data.dyngnn import DTDGPipeline, dataset_from_snapshots
    from repro.serve import IngestSpec, ServeConfig, ServeEngine

    n = rr.cfg.num_nodes
    nb = SERVE_WINDOWS // SERVE_BLOCK
    stream = ctdg.synthetic_ctdg(n, SERVE_EVENTS, delete_frac=0.2,
                                 seed=SEED).sorted()
    ds = dataset_from_snapshots(ctdg.snapshot_events(stream, SERVE_WINDOWS),
                                n)
    pipe = DTDGPipeline(ds, nb=nb)
    cfg = dataclasses.replace(rr.cfg, num_steps=SERVE_WINDOWS,
                              checkpoint_blocks=nb)
    params = res.state.params
    spec = IngestSpec(num_windows=SERVE_WINDOWS,
                      time_range=(float(stream.time.min()),
                                  float(stream.time.max())),
                      block_size=pipe.bsize, max_edges=pipe.max_edges)
    eng = ServeEngine(ServeConfig(model=cfg, ingest=spec), params=params)
    chunk = len(stream) // 8 + 1
    for lo in range(0, len(stream), chunk):
        sl = slice(lo, lo + chunk)
        eng.ingest(ctdg.EventStream(stream.src[sl], stream.dst[sl],
                                    stream.time[sl], stream.kind[sl], n))
    eng.advance_all()
    rng = np.random.default_rng(SEED + 1)
    ids = rng.choice(n, 64, replace=False)
    pairs = rng.integers(0, n, size=(8, 2))
    got = [eng.query_nodes(ids[:1]), eng.query_nodes(ids[:8]),
           eng.query_nodes(ids), eng.query_links(pairs)]
    z = ckpt.blocked_forward(cfg, params, pipe.batch, nb)[-1]
    want = [np.asarray(mdl.classify(params, z[jnp.asarray(ids[:k])]))
            for k in (1, 8, 64)]
    want.append(np.asarray(mdl.link_logits(params, z,
                                           jnp.asarray(pairs, jnp.int32))))
    err = max(float(np.max(np.abs(g - w))) for g, w in zip(got, want,
                                                          strict=True))
    r = eng.result()
    log(f"serve: N={n}, {r.events_ingested} events in {SERVE_WINDOWS} "
        f"windows ({ds.snapshots[-1].shape[0]} alive edges at the end), "
        f"{r.queries} query calls, p50 {r.p50_ms!r} ms p95 {r.p95_ms!r} ms")
    log(f"serve: max abs gap served vs offline forward {err!r} "
        f"(tolerance {SERVE_ATOL})")
    check(bool(np.all(np.isfinite(got[2]))) and err <= SERVE_ATOL,
          f"served scores off the offline forward by {err}")


def mesh_phase(cfg, data, opt, clock: CompileClock) -> None:
    """streamed_mesh on four chips vs the same plan on one shard."""
    from repro.run import Engine, ExecutionPlan, InMemoryDTDG, RunConfig

    def fit(shards, source):
        eng = Engine(RunConfig(
            model=cfg, data=source, optimizer=opt, seed=SEED, log_every=4,
            log_fn=log, plan=ExecutionPlan(mode="streamed_mesh",
                                           shards=shards,
                                           num_epochs=MESH_EPOCHS)))
        t0 = time.perf_counter()
        rr = eng.resolve()
        setup_s = time.perf_counter() - t0
        c0, t0 = clock.seconds, time.perf_counter()
        res = eng.fit()
        log(f"mesh P={shards}: host setup {setup_s!r} s, fit "
            f"{time.perf_counter() - t0!r} s of which backend compile "
            f"{clock.seconds - c0!r} s; losses {res.losses}")
        return rr, res

    rr4, res4 = fit(4, data)
    peaks = [peak_bytes(d) for d in jax.devices()]
    log(f"mesh P=4: N={rr4.ds.num_nodes} T={rr4.ds.num_steps} rounds of "
        f"{rr4.pipeline.bsize}; per-device peak bytes {peaks}")
    _, res1 = fit(1, InMemoryDTDG(rr4.ds, pipeline=rr4.pipeline))
    l4, l1 = np.asarray(res4.losses), np.asarray(res1.losses)
    gap = float(np.max(np.abs(l4 - l1) / np.abs(l1)))
    log(f"mesh: max relative loss gap P=4 vs P=1 {gap!r} "
        f"(tolerance {LOSS_RTOL})")
    check(l4.shape == l1.shape and bool(np.all(np.isfinite(l4))),
          "mesh losses missing or non-finite")
    check(gap <= LOSS_RTOL, f"P=4/P=1 loss gap {gap} > {LOSS_RTOL}")
    check(all(p > 0 for p in peaks[:4]), f"a device held nothing: {peaks}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = run only the streamed_mesh P=4 phase and its "
                         "P=1 comparison")
    args = ap.parse_args(argv)
    sys.path.insert(0, SRC)
    devs = device_phase(args.chips)
    from repro.configs import paper_dyngnn, registry
    from repro.launch.compile_cache import enable_compile_cache
    from repro.optim import adamw

    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    cfg = registry.get_arch("tmgcn").make_config()
    data = paper_dyngnn.synthetic_trace(cfg, num_steps=T_CUT)
    log(f"config: {cfg}; trace {data}")
    opt = adamw.AdamWConfig(lr=1e-2, warmup_steps=10,
                            total_steps=EPOCHS * T_CUT, weight_decay=0.0)
    if args.chips == 4:
        mesh_phase(cfg, data, opt, clock)
    else:
        kernel_phase(data.num_nodes, cfg)
        rr, res = train_phase(cfg, data, opt, clock)
        cpu_reference_phase(rr, res, opt)
        serve_phase(rr, res)
    log(f"compile: backend compile {clock.seconds!r} s in total, "
        f"{clock.hits} persistent-cache hits")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
