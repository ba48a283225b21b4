"""Golden tests for the declarative ``repro.run`` Engine API.

``Engine.fit()`` must reproduce the legacy entrypoints' loss streams
BIT-FOR-BIT on every schedule (eager, streamed, streamed_mesh): the
Engine is plumbing, never math.  Plus: seed plumbing, the plan's
auto-pad / re-block behavior, the ``EdgeListDTDG`` file round-trip, the
deprecation contract of the shims, and checkpoint resume."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.models import DynGNNConfig
from repro.data.dyngnn import (DTDGPipeline, dataset_from_snapshots,
                               synthetic_dataset)
from repro.graph import generate
from repro.optim import adamw
from repro.run import (CheckpointSpec, Engine, EdgeListDTDG, ExecutionPlan,
                       InMemoryDTDG, RunConfig, SyntheticTrace,
                       read_edgelist, write_edgelist)
from repro.train import trainer

N, T = 48, 16


def _silent(_msg):
    return None


def _cfg(model="tmgcn", n=N, t=T, nb=2):
    return DynGNNConfig(model=model, num_nodes=n, num_steps=t, window=3,
                        checkpoint_blocks=nb)


def _src(model="tmgcn", n=N, t=T):
    smooth = {"tmgcn": "mproduct", "evolvegcn": "edgelife",
              "cdgcn": "none"}[model]
    return SyntheticTrace(num_nodes=n, num_steps=t, density=2.0, churn=0.1,
                          smoothing_mode=smooth, window=3)


def _engine(cfg, data, plan, **kw):
    kw.setdefault("log_fn", _silent)
    return Engine(RunConfig(model=cfg, data=data, plan=plan, **kw))


# ------------------------------------------------ golden equivalence -------

def test_eager_single_device_matches_manual_loop():
    """Engine eager (1 device) == a hand-rolled loop over the legacy step
    factory with the legacy defaults (PRNGKey(0), default AdamW)."""
    cfg = _cfg()
    ds = _src().build()
    num_steps = 12
    got = _engine(cfg, InMemoryDTDG(ds),
                  ExecutionPlan(mode="eager", num_steps=num_steps)).fit()

    pipe = DTDGPipeline(ds, nb=cfg.checkpoint_blocks)
    opt_cfg = adamw.AdamWConfig(lr=1e-2, warmup_steps=10,
                                total_steps=num_steps, weight_decay=0.0)
    params = trainer.dyn_models.init_params(jax.random.PRNGKey(0), cfg)
    opt_state = adamw.init_state(params)
    step_fn = trainer.make_single_device_train_step(cfg, opt_cfg)
    lab = jnp.asarray(ds.labels)
    want = []
    for _ in range(num_steps):
        params, opt_state, loss = step_fn(params, opt_state, pipe.batch,
                                          lab)
        want.append(float(loss))
    assert got.losses == want
    assert got.state.step == num_steps


@pytest.mark.parametrize("model", ["tmgcn", "cdgcn", "evolvegcn"])
def test_streamed_matches_train_streamed(model):
    """Engine streamed == the stream loop called the way the legacy shim
    called it (pipeline-derived block size / stats / max_edges)."""
    from repro.stream import train_loop as stream_train
    cfg = _cfg(model, t=8)
    ds = _src(model, t=8).build()
    pipe = DTDGPipeline(ds, nb=cfg.checkpoint_blocks)
    got = _engine(cfg, InMemoryDTDG(ds, pipeline=pipe),
                  ExecutionPlan(mode="streamed", num_epochs=2)).fit()
    ref = stream_train.train_streamed(
        cfg, ds.snapshots, ds.values, np.asarray(ds.frames),
        np.asarray(ds.labels), block_size=pipe.bsize, num_epochs=2,
        stats=pipe.stream_stats, max_edges=pipe.max_edges)
    assert got.losses == ref.losses
    for a, b in zip(jax.tree.leaves(got.state.params),
                    jax.tree.leaves(ref.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert got.stream_report is not None
    assert got.transfer_report["graph_diff"] > 0


def test_streamed_replays_the_kept_trace(monkeypatch):
    """The pipeline keeps the encoded trace within its host budget; every
    epoch of every fit stages those items, which nothing writes to (the
    delta-apply donates the device buffers they were staged into), and
    trains as a pipeline that re-encodes each epoch does."""
    from repro.data import dyngnn
    from repro.stream import encoder as stream_encoder
    cfg = _cfg("cdgcn", t=8)
    ds = _src("cdgcn", t=8).build()
    pipe = DTDGPipeline(ds, nb=cfg.checkpoint_blocks)
    assert pipe.stream_items is not None
    assert len(pipe.stream_items) == ds.num_steps
    eng = _engine(cfg, InMemoryDTDG(ds, pipeline=pipe),
                  ExecutionPlan(mode="streamed"))
    first, second = eng.fit().losses, eng.fit().losses
    fresh = stream_encoder.iter_encode_stream(
        ds.snapshots, ds.values, ds.num_nodes, pipe.max_edges, pipe.bsize,
        pipe.stream_stats)
    for kept, new in zip(pipe.stream_items, fresh, strict=True):
        for k, v in vars(new).items():
            np.testing.assert_array_equal(getattr(kept, k), v)
    monkeypatch.setattr(dyngnn, "HOST_STREAM_CACHE_BYTES", 0)
    lazy = DTDGPipeline(ds, nb=cfg.checkpoint_blocks)
    assert lazy.stream_items is None
    assert lazy.transfer_bytes() == pipe.transfer_bytes()
    want = _engine(cfg, InMemoryDTDG(ds, pipeline=lazy),
                   ExecutionPlan(mode="streamed")).fit().losses
    assert first == second == want


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 host devices")
def test_streamed_mesh_matches_distributed_loop():
    """Engine streamed_mesh == train_distributed_streamed on the same
    trace, and overlap stays a pure schedule change through the Engine."""
    from repro.launch.mesh import make_host_mesh
    from repro.stream import distributed as dist
    cfg = _cfg()
    ds = _src().build()
    pipe = DTDGPipeline(ds, nb=cfg.checkpoint_blocks)
    got = _engine(cfg, InMemoryDTDG(ds, pipeline=pipe),
                  ExecutionPlan(mode="streamed_mesh", shards=4,
                                num_epochs=2)).fit()
    ref = dist.train_distributed_streamed(
        cfg, ds.snapshots, ds.values, np.asarray(ds.frames),
        np.asarray(ds.labels), mesh=make_host_mesh(data=4, model=1),
        num_epochs=2, stats=pipe.stream_stats, max_edges=pipe.max_edges)
    assert got.losses == ref.losses
    assert got.per_shard_bytes == ref.per_shard_bytes
    for a, b in zip(jax.tree.leaves(got.state.params),
                    jax.tree.leaves(ref.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    sync = _engine(cfg, InMemoryDTDG(ds, pipeline=pipe),
                   ExecutionPlan(mode="streamed_mesh", shards=4,
                                 num_epochs=2, overlap=False)).fit()
    assert sync.losses == got.losses


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 host devices")
def test_eager_mesh_matches_legacy_shim():
    """The deprecated entrypoint and the Engine agree under a mesh (the
    shim IS a RunConfig constructor — this pins its plumbing)."""
    from repro.launch.mesh import make_host_mesh
    cfg = _cfg()
    ds = _src().build()
    pipe = DTDGPipeline(ds, nb=cfg.checkpoint_blocks)
    mesh = make_host_mesh(data=4, model=1)
    with pytest.warns(DeprecationWarning, match="train_dyngnn"):
        state, losses = trainer.train_dyngnn(cfg, pipe, mesh=mesh,
                                             num_steps=6,
                                             log_fn=_silent)
    got = _engine(cfg, InMemoryDTDG(ds, pipeline=pipe),
                  ExecutionPlan(mode="eager", mesh=mesh,
                                num_steps=6)).fit()
    assert got.losses == losses
    assert got.state.step == state.step


def test_legacy_streamed_shim_warns_and_matches():
    cfg = _cfg(t=8)
    ds = _src(t=8).build()
    pipe = DTDGPipeline(ds, nb=cfg.checkpoint_blocks)
    with pytest.warns(DeprecationWarning, match="train_dyngnn_streamed"):
        state, losses = trainer.train_dyngnn_streamed(cfg, pipe,
                                                      log_fn=_silent)
    got = _engine(cfg, InMemoryDTDG(ds, pipeline=pipe),
                  ExecutionPlan(mode="streamed")).fit()
    assert got.losses == losses
    assert isinstance(losses, list) and isinstance(state.step, int)


# ------------------------------------------------------ seed / plan --------

def test_seed_is_plumbed():
    """RunConfig.seed reaches param init (no more hard-coded PRNGKey(0))."""
    cfg = _cfg(t=8)
    ds = _src(t=8).build()
    runs = {}
    for seed in (0, 1):
        runs[seed] = _engine(cfg, InMemoryDTDG(ds),
                             ExecutionPlan(mode="eager", num_steps=4),
                             seed=seed).fit()
    assert runs[0].losses != runs[1].losses
    # seed=0 reproduces the legacy PRNGKey(0) stream
    again = _engine(cfg, InMemoryDTDG(ds),
                    ExecutionPlan(mode="eager", num_steps=4), seed=0).fit()
    assert again.losses == runs[0].losses


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 host devices")
def test_plan_auto_pads_num_nodes_and_logs():
    """50 nodes over 4 shards: the plan pads to 52 instead of dying."""
    msgs = []
    cfg = _cfg(n=50)
    eng = _engine(cfg, _src(n=50),
                  ExecutionPlan(mode="streamed_mesh", shards=4),
                  log_fn=msgs.append)
    rr = eng.resolve()
    assert rr.cfg.num_nodes == 52
    assert rr.padded_from == 50
    assert any("auto-padding num_nodes 50 -> 52" in m for m in msgs)
    res = eng.fit()
    assert len(res.losses) == T // rr.pipeline.bsize
    assert np.isfinite(res.losses).all()


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 host devices")
def test_plan_reblocks_timeline_for_mesh():
    """nb=8 gives block size 2, not divisible over 4 shards: the plan
    re-blocks (largest legal block <= requested) instead of raising."""
    msgs = []
    cfg = _cfg(nb=8)
    eng = _engine(cfg, _src(), ExecutionPlan(mode="streamed_mesh",
                                             shards=4),
                  log_fn=msgs.append)
    rr = eng.resolve()
    assert rr.cfg.checkpoint_blocks == 4          # bsize 4 == P
    assert rr.pipeline.bsize % 4 == 0
    assert any("re-blocking" in m for m in msgs)
    res = eng.fit()
    assert len(res.losses) == 4


def test_plan_validation():
    with pytest.raises(ValueError, match="plan.mode"):
        ExecutionPlan(mode="magic").validate()
    with pytest.raises(ValueError, match="single-device"):
        ExecutionPlan(mode="streamed", shards=4).validate()
    with pytest.raises(ValueError, match="a2a_chunks must be"):
        ExecutionPlan(a2a_chunks=0).validate()
    with pytest.raises(ValueError, match="a2a_chunks"):
        ExecutionPlan(mode="streamed", a2a_chunks=2).validate()
    # meshless eager has no all-to-alls either: chunking must fail
    # loudly, not silently no-op (RunResult echoes the knob as executed)
    with pytest.raises(ValueError, match="without a mesh"):
        ExecutionPlan(mode="eager", shards=1, a2a_chunks=2).validate()
    with pytest.raises(ValueError, match="pipeline_rounds"):
        ExecutionPlan(mode="eager", pipeline_rounds=True).validate()
    # mesh schedules accept both knobs
    ExecutionPlan(mode="streamed_mesh", shards=4, a2a_chunks=4,
                  pipeline_rounds=True).validate()
    ExecutionPlan(mode="eager", shards=4, a2a_chunks=2).validate()


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 host devices")
def test_streamed_mesh_pipelined_matches_serial_through_engine():
    """The acceptance bar of the chunked-round pipeline: a2a_chunks=4 +
    pipeline_rounds=True on the 8-device host mesh reproduces the serial
    plan's loss stream at <= 1e-5 relative, and the RunResult echoes the
    knobs it ran with."""
    cfg = _cfg()
    ds = _src().build()
    pipe = DTDGPipeline(ds, nb=cfg.checkpoint_blocks)
    serial = _engine(cfg, InMemoryDTDG(ds, pipeline=pipe),
                     ExecutionPlan(mode="streamed_mesh", shards=8,
                                   num_epochs=2)).fit()
    piped = _engine(cfg, InMemoryDTDG(ds, pipeline=pipe),
                    ExecutionPlan(mode="streamed_mesh", shards=8,
                                  num_epochs=2, a2a_chunks=4,
                                  pipeline_rounds=True)).fit()
    assert len(piped.losses) == len(serial.losses)
    np.testing.assert_allclose(piped.losses, serial.losses, rtol=1e-5)
    assert piped.a2a_chunks == 4 and piped.pipeline_rounds is True
    assert serial.a2a_chunks == 1 and serial.pipeline_rounds is False
    assert piped.per_shard_bytes == serial.per_shard_bytes


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 host devices")
def test_eager_mesh_chunked_a2a_matches_serial():
    """a2a_chunks also threads through the eager shard_map schedule
    (snapshot_partition_loss) without changing the loss stream."""
    cfg = _cfg()
    ds = _src().build()
    plain = _engine(cfg, InMemoryDTDG(ds),
                    ExecutionPlan(mode="eager", shards=4,
                                  num_steps=6)).fit()
    chunked = _engine(cfg, InMemoryDTDG(ds),
                      ExecutionPlan(mode="eager", shards=4, num_steps=6,
                                    a2a_chunks=2)).fit()
    np.testing.assert_allclose(chunked.losses, plain.losses, rtol=1e-5)
    assert chunked.a2a_chunks == 2


# ------------------------------------------------ edge-list round-trip -----

@pytest.mark.parametrize("ext", ["tsv", "npz"])
def test_edgelist_roundtrip_matches_in_memory(tmp_path, ext):
    """write trace -> load -> identical dataset AND identical losses."""
    snaps = generate.evolving_dynamic_graph(N, 8, density=2.0, churn=0.2,
                                            seed=3)
    path = tmp_path / f"trace.{ext}"
    write_edgelist(path, snaps)
    loaded_snaps, n_seen = read_edgelist(path)
    assert len(loaded_snaps) == len(snaps)
    for a, b in zip(loaded_snaps, snaps):
        assert np.array_equal(a, b)
    assert n_seen <= N

    mem = dataset_from_snapshots(snaps, N, smoothing_mode="mproduct",
                                 window=3)
    src = EdgeListDTDG(str(path), num_nodes=N, smoothing_mode="mproduct",
                       window=3)
    loaded = src.build()
    assert loaded.num_nodes == mem.num_nodes
    for a, b in zip(loaded.snapshots, mem.snapshots):
        assert np.array_equal(a, b)
    np.testing.assert_array_equal(loaded.frames, mem.frames)
    np.testing.assert_array_equal(loaded.labels, mem.labels)

    cfg = _cfg(t=8)
    plan = ExecutionPlan(mode="streamed")
    from_file = _engine(cfg, src, plan).fit()
    from_mem = _engine(cfg, InMemoryDTDG(mem), plan).fit()
    assert from_file.losses == from_mem.losses


@pytest.mark.parametrize("ext", ["tsv", "npz"])
def test_edgelist_preserves_empty_boundary_snapshots(tmp_path, ext):
    """The num_steps marker keeps empty leading/trailing snapshots, so
    write -> load never silently shortens the trace."""
    core = generate.evolving_dynamic_graph(16, 4, density=2.0, seed=1)
    empty = np.zeros((0, 2), dtype=np.int32)
    snaps = [empty] + core + [empty]
    path = tmp_path / f"trace.{ext}"
    write_edgelist(path, snaps)
    loaded, _ = read_edgelist(path)
    assert len(loaded) == len(snaps) == 6
    for a, b in zip(loaded, snaps):
        assert np.array_equal(a, b)


def test_synthetic_trace_padding_pads_not_regenerates():
    """A num_nodes override appends isolated vertices to the NOMINAL
    trace — same graph, same labels — never a new random graph."""
    src = _src(n=50)
    nominal = src.build()
    padded = src.build(num_nodes=52)
    assert padded.num_nodes == 52
    for a, b in zip(padded.snapshots, nominal.snapshots):
        assert np.array_equal(a, b)
    np.testing.assert_array_equal(padded.frames[:, :50], nominal.frames)
    np.testing.assert_array_equal(padded.labels[:, :50], nominal.labels)
    assert not padded.frames[:, 50:].any()
    with pytest.raises(ValueError, match="shrink"):
        src.build(num_nodes=40)


def test_edgelist_padding_keeps_real_labels(tmp_path):
    """Padding an edge-list source appends isolated vertices AFTER label
    derivation — pad nodes can never shift the real nodes' label median."""
    snaps = generate.evolving_dynamic_graph(30, 4, density=2.0, seed=5)
    p = tmp_path / "t.tsv"
    write_edgelist(p, snaps)
    src = EdgeListDTDG(str(p), num_nodes=30)
    base = src.build()
    padded = src.build(num_nodes=32)
    assert padded.num_nodes == 32
    np.testing.assert_array_equal(padded.labels[:, :30], base.labels)
    np.testing.assert_array_equal(padded.frames[:, :30], base.frames)
    assert not padded.frames[:, 30:].any()


def test_checkpoint_rejected_on_streamed_plans():
    """No silent checkpoint drops: a CheckpointSpec on a streamed plan
    fails loudly at resolve time."""
    cfg = _cfg(t=8)
    eng = _engine(cfg, _src(t=8), ExecutionPlan(mode="streamed"),
                  checkpoint=CheckpointSpec("/tmp/never-used"))
    with pytest.raises(ValueError, match="only wired for plan.mode"):
        eng.resolve()


def test_edgelist_rejects_bad_shapes(tmp_path):
    p = tmp_path / "bad.tsv"
    p.write_text("# src dst\n0\t1\n2\t3\n")
    with pytest.raises(ValueError, match="columns"):
        read_edgelist(p)
    with pytest.raises(ValueError, match="node ids up to"):
        snaps = [np.array([[0, 5]], dtype=np.int32)]
        q = tmp_path / "big.tsv"
        write_edgelist(q, snaps)
        EdgeListDTDG(str(q), num_nodes=3).build()


# --------------------------------------------------- resume / evaluate -----

def test_engine_resume_roundtrip(tmp_path):
    cfg = _cfg(model="cdgcn")
    data = _src("cdgcn")
    ck = CheckpointSpec(str(tmp_path / "ck"), every=5)
    first = _engine(cfg, data, ExecutionPlan(mode="eager", num_steps=10),
                    checkpoint=ck).fit()
    assert first.state.step == 10
    eng2 = _engine(cfg, data, ExecutionPlan(mode="eager", num_steps=15),
                   checkpoint=ck)
    res = eng2.resume()
    assert res.state.step == 15
    assert len(res.losses) == 5               # only steps 10..14 re-run

    with pytest.raises(ValueError, match="RunConfig.checkpoint"):
        _engine(cfg, data, ExecutionPlan(mode="eager", num_steps=5)
                ).resume()
    with pytest.raises(FileNotFoundError):
        _engine(cfg, data, ExecutionPlan(mode="eager", num_steps=5),
                checkpoint=CheckpointSpec(str(tmp_path / "empty"))
                ).resume()


def test_engine_evaluate_needs_fit_or_state():
    cfg = _cfg(t=8)
    eng = _engine(cfg, _src(t=8), ExecutionPlan(mode="eager", num_steps=2))
    with pytest.raises(ValueError, match="before fit"):
        eng.evaluate()
    res = eng.fit()
    acc = eng.evaluate(res)
    assert 0.0 <= acc <= 1.0


# ----------------------------------------- out-of-core edge-list read ------

@pytest.mark.parametrize("ext", ["tsv", "npz"])
def test_edgelist_out_of_core_matches_in_memory(tmp_path, ext):
    """chunk_edges (chunked text scan / zip-member memmap) bins the
    SAME snapshots as the monolithic read, at every chunk size."""
    snaps = generate.evolving_dynamic_graph(N, 8, density=2.0, churn=0.2,
                                            seed=5)
    snaps[2] = np.zeros((0, 2), dtype=np.int32)     # empty mid-trace bin
    path = tmp_path / f"trace.{ext}"
    write_edgelist(path, snaps)
    ref, n_ref = read_edgelist(path)
    for chunk in (1, 13, 10_000):
        got, n = read_edgelist(path, chunk_edges=chunk)
        assert n == n_ref
        assert len(got) == len(ref)
        for a, b in zip(got, ref):
            assert np.array_equal(a, b)


def test_edgelist_out_of_core_npz_is_memmapped(tmp_path):
    """Uncompressed npz members really are mapped, not loaded — and a
    deflated archive falls back to the regular load, same snapshots."""
    from repro.run.data import _npz_memmaps

    snaps = generate.evolving_dynamic_graph(24, 4, density=2.0, seed=2)
    p = tmp_path / "trace.npz"
    write_edgelist(p, snaps)
    mm = _npz_memmaps(p)
    assert mm is not None
    assert isinstance(mm["src"], np.memmap)
    assert np.array_equal(np.asarray(mm["src"]),
                          np.concatenate([s[:, 0] for s in snaps]))
    src = np.concatenate([s[:, 0] for s in snaps]).astype(np.int64)
    dst = np.concatenate([s[:, 1] for s in snaps]).astype(np.int64)
    t = np.concatenate([np.full(s.shape[0], i, np.int64)
                        for i, s in enumerate(snaps)])
    pc = tmp_path / "comp.npz"
    np.savez_compressed(pc, src=src, dst=dst, t=t, num_steps=np.int64(4))
    assert _npz_memmaps(pc) is None         # deflated: nothing to map
    got, _ = read_edgelist(pc, chunk_edges=7)
    ref, _ = read_edgelist(pc)
    for a, b in zip(got, ref):
        assert np.array_equal(a, b)


def test_edgelist_source_out_of_core_trains_identically(tmp_path):
    """EdgeListDTDG(chunk_edges=...) builds the same dataset, so the
    same run produces the same losses."""
    snaps = generate.evolving_dynamic_graph(N, 8, density=2.0, seed=7)
    path = tmp_path / "trace.tsv"
    write_edgelist(path, snaps)
    cfg = _cfg(model="cdgcn", t=8)
    plan = ExecutionPlan(mode="streamed")
    a = _engine(cfg, EdgeListDTDG(str(path), num_nodes=N,
                                  smoothing_mode="none"), plan).fit()
    b = _engine(cfg, EdgeListDTDG(str(path), num_nodes=N,
                                  smoothing_mode="none",
                                  chunk_edges=16), plan).fit()
    assert a.losses == b.losses


# -------------------------------------------------- fetch_data + fixture ---

def test_fetch_data_fixture_pipeline(tmp_path):
    """The committed fixture is byte-reproducible from the tool's
    deterministic sample through the same preprocessing path the real
    fetch uses, and loads through EdgeListDTDG."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from tools import fetch_data as fd

    raw = tmp_path / "out.epinions-sample"
    fd.make_sample(raw)
    out = tmp_path / "epinions_tiny.tsv"
    fd.make_fixture(raw, out, num_nodes=24, num_steps=8)
    committed = Path(__file__).parent / "fixtures" / "epinions_tiny.tsv"
    assert out.read_text() == committed.read_text()
    ds = EdgeListDTDG(str(committed)).build()
    assert ds.num_nodes == 24 and ds.num_steps == 8
    ds_ooc = EdgeListDTDG(str(committed), chunk_edges=8).build()
    for a, b in zip(ds.snapshots, ds_ooc.snapshots):
        assert np.array_equal(a, b)


def test_fetch_data_preprocess_and_checksum(tmp_path):
    """preprocess bins KONECT rows into a loadable trace; the checksum
    layer records on first sight and refuses a tampered file."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from tools import fetch_data as fd

    raw = tmp_path / "out.sample"
    fd.make_sample(raw, num_nodes=40, num_edges=200, seed=11)
    out = tmp_path / "trace.tsv"
    fd.preprocess(raw, out, num_steps=6)
    snaps, n = read_edgelist(out)
    assert len(snaps) == 6
    assert sum(s.shape[0] for s in snaps) == 200
    assert n <= 40

    # trust-on-first-use sidecar, then verification
    digest = fd.verify_checksum(raw, None, None)
    sidecar = raw.with_suffix(raw.suffix + ".sha256")
    assert sidecar.exists() and digest in sidecar.read_text()
    assert fd.verify_checksum(raw, None, None) == digest
    with open(raw, "a") as f:
        f.write("9 9 1 9\n")
    with pytest.raises(SystemExit, match="checksum mismatch"):
        fd.verify_checksum(raw, None, None)
    with pytest.raises(SystemExit, match="checksum mismatch"):
        fd.verify_checksum(raw, digest, None)


def test_fetch_data_sub_slice_deterministic():
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from tools import fetch_data as fd

    rng = np.random.default_rng(0)
    src = rng.integers(1, 100, 500)
    dst = rng.integers(1, 100, 500)
    ts = rng.integers(0, 1000, 500)
    a = fd.sub_slice(src, dst, ts, 20)
    b = fd.sub_slice(src, dst, ts, 20)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    kept = np.unique(np.concatenate([a[0], a[1]]))
    assert kept.shape[0] <= 20
    # kept ids are the first 20 distinct ids in file order
    seen = []
    for s, d in zip(src, dst):
        for v in (s, d):
            if v not in seen:
                seen.append(v)
        if len(seen) >= 20:
            break
    assert set(kept) <= set(seen[:21])
