"""Streamed graph-diff transfer subsystem vs the core.graphdiff reference.

The reference encoder/decoder (``core.graphdiff``) is the semantic
ground truth; the vectorized encoder, the stats pad sizing, the prefetch
path, and the shard-aware slicing must all reproduce it exactly."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graphdiff, smoothing
from repro.core.models import DynGNNConfig
from repro.graph import generate
from repro.stream import encoder as stream_encoder
from repro.stream import sharded as stream_sharded
from repro.stream import train_loop as stream_train
from repro.stream.prefetch import DeltaApplier, PrefetchIterator, stage_item

N, T, BS = 96, 16, 4


def _trace(churn=0.15, smooth="mproduct", seed=0):
    snaps = generate.evolving_dynamic_graph(N, T, density=3.0, churn=churn,
                                            seed=seed)
    values = None
    if smooth == "mproduct":
        snaps, values = smoothing.m_transform_sparse(snaps, 3)
    elif smooth == "edgelife":
        snaps, values = smoothing.edge_life(snaps, 3)
    max_edges = stream_encoder.padded_max_edges(snaps)
    return snaps, values, max_edges


@pytest.mark.parametrize("smooth", ["none", "mproduct", "edgelife"])
@pytest.mark.parametrize("churn", [0.05, 0.3])
def test_fast_encoder_decodes_bit_identical(smooth, churn):
    """Vectorized encoder == dict-based reference: decoded (edges, mask)
    and shipped values are exactly equal on a random CTDG trace."""
    snaps, values, max_edges = _trace(churn=churn, smooth=smooth)
    ref = graphdiff.encode_stream(snaps, values, N, max_edges, BS)
    fast = stream_encoder.encode_stream_fast(snaps, values, N, max_edges,
                                             BS)
    dec_ref = graphdiff.decode_stream(ref, max_edges)
    dec_fast = graphdiff.decode_stream(fast, max_edges)
    for (e1, m1), (e2, m2) in zip(dec_ref, dec_fast):
        assert np.array_equal(e1, e2)
        assert np.array_equal(m1, m2)
    for a, b in zip(ref, fast):
        assert np.array_equal(a.values, b.values)
        assert a.num_edges == b.num_edges


def test_stats_pads_bound_churn_and_shrink_buffers():
    snaps, values, max_edges = _trace()
    stats = stream_encoder.measure_stats(snaps, N, BS, max_edges)
    stream = stream_encoder.encode_stream_fast(snaps, values, N, max_edges,
                                               BS, stats)
    deltas = [s for s in stream if isinstance(s, graphdiff.SnapshotDelta)]
    assert deltas, "trace produced no delta steps"
    for d in deltas:
        assert d.drop_pos.shape == (stats.max_drops,)
        assert d.add_edges.shape == (stats.max_adds, 2)
        assert int(d.drop_mask.sum()) <= stats.max_drops
        assert int(d.add_mask.sum()) <= stats.max_adds
    # stats pads genuinely tighter than the E_max pads the reference uses
    assert stats.max_drops < max_edges


def test_payload_bytes_match_reference_and_ratio_bound():
    """Valid-lane byte accounting is pad-independent: fast == reference,
    and the stream beats the naive full-transfer baseline while staying
    above the block-boundary lower bound (full snapshots every BS steps
    must ship >= T/BS full payloads)."""
    snaps, values, max_edges = _trace()
    ref = graphdiff.encode_stream(snaps, values, N, max_edges, BS)
    fast = stream_encoder.encode_stream_fast(snaps, values, N, max_edges,
                                             BS)
    for a, b in zip(ref, fast):
        assert a.payload_bytes == b.payload_bytes
    gd = graphdiff.stream_bytes(fast)
    naive = graphdiff.naive_bytes(snaps)
    assert 0 < gd < naive
    full_bytes = sum(s.payload_bytes for s in fast
                     if isinstance(s, graphdiff.FullSnapshot))
    assert gd >= full_bytes > 0


def test_encoder_churn_overflow_resyncs_instead_of_crashing():
    """When live churn exceeds the stats-sized pads the encoder must not
    raise mid-stream: it ships a FullSnapshot resync for that step, counts
    it, and the stream still decodes to the exact snapshot sequence."""
    snaps, values, max_edges = _trace(churn=0.3)
    tiny = stream_encoder.DeltaStats(max_edges=max_edges, max_drops=1,
                                     max_adds=1)
    report = stream_encoder.StreamReport()
    with pytest.warns(UserWarning, match="resync"):
        stream = stream_encoder.encode_stream_fast(
            snaps, values, N, max_edges, BS, tiny, report=report)
    assert report.resyncs > 0
    assert report.worst_drops > tiny.max_drops \
        or report.worst_adds > tiny.max_adds
    assert len(report.resync_steps) == report.resyncs
    fulls = sum(isinstance(s, graphdiff.FullSnapshot) for s in stream)
    assert fulls == T // BS + report.resyncs
    # degraded, not wrong: every step still reconstructs its snapshot
    for (e, m), snap in zip(graphdiff.decode_stream(stream, max_edges),
                            snaps):
        valid = e[m > 0]
        assert set(map(tuple, valid.tolist())) \
            == set(map(tuple, snap.tolist()))


def test_encoder_churn_overflow_strict_mode_raises():
    snaps, values, max_edges = _trace(churn=0.3)
    tiny = stream_encoder.DeltaStats(max_edges=max_edges, max_drops=1,
                                     max_adds=1)
    with pytest.raises(stream_encoder.ChurnOverflowError,
                       match="exceeds stats pad"):
        stream_encoder.encode_stream_fast(snaps, values, N, max_edges, BS,
                                          tiny, on_overflow="raise")


def test_prefetch_iterator_preserves_order_and_propagates_errors():
    items = list(range(20))
    out = list(PrefetchIterator(iter(items), stage_fn=lambda x: x * 2,
                                depth=3))
    assert out == [x * 2 for x in items]

    def bad():
        yield 1
        raise RuntimeError("encoder blew up")

    it = PrefetchIterator(bad(), stage_fn=lambda x: x, depth=2)
    assert next(it) == 1
    with pytest.raises(RuntimeError, match="encoder blew up"):
        list(it)
    # terminated stays terminated (no deadlock, no re-raise loop)
    with pytest.raises(StopIteration):
        next(it)


def test_prefetch_iterator_close_unblocks_abandoned_worker():
    """Abandoning the stream mid-flight must retire the worker thread
    even while it is blocked on a full queue (infinite producer)."""
    import itertools
    it = PrefetchIterator(itertools.count(), stage_fn=lambda x: x, depth=2)
    assert next(it) == 0
    it.close()
    assert not it._thread.is_alive()
    with pytest.raises(StopIteration):
        next(it)


def test_prefetch_worker_exception_before_first_next():
    """An encoder that dies immediately re-raises on the FIRST __next__
    (not a hang, not a swallowed error)."""
    def dead():
        raise RuntimeError("dead on arrival")
        yield  # pragma: no cover

    it = PrefetchIterator(dead(), stage_fn=lambda x: x, depth=2)
    with pytest.raises(RuntimeError, match="dead on arrival"):
        next(it)
    with pytest.raises(StopIteration):
        next(it)


def test_prefetch_stage_fn_exception_propagates():
    """Errors raised while STAGING (device_put path) surface like encoder
    errors: re-raised at the consumer, then terminated."""
    def boom(x):
        if x == 3:
            raise ValueError("stage failed")
        return x

    it = PrefetchIterator(iter(range(10)), stage_fn=boom, depth=2)
    got = [next(it), next(it), next(it)]
    assert got == [0, 1, 2]
    with pytest.raises(ValueError, match="stage failed"):
        list(it)


def test_prefetch_close_releases_staged_buffers_and_is_idempotent():
    """close() during backpressure drains every staged item (releasing the
    buffers), retires the worker, and is safe to call repeatedly /
    via the context-manager protocol."""
    import itertools
    staged: list[int] = []

    def stage(x):
        staged.append(x)
        return x

    it = PrefetchIterator(itertools.count(), stage_fn=stage, depth=3)
    assert next(it) == 0
    it.close()
    it.close()                      # idempotent
    assert not it._thread.is_alive()
    assert it._q.qsize() == 0       # staged buffers dropped
    assert len(staged) >= 1         # worker really was ahead of us
    with pytest.raises(StopIteration):
        next(it)
    # context-manager form retires the worker on exit too
    with PrefetchIterator(itertools.count(), stage_fn=lambda x: x,
                          depth=2) as cm:
        assert next(cm) == 0
    assert not cm._thread.is_alive()


@pytest.mark.parametrize("donate", [True, False])
def test_delta_applier_multi_shard_ring(donate):
    """One donated edge-buffer ring per device shard, consumed interleaved
    (the distributed trainer's schedule): every shard's ring reproduces
    its own stream's decode exactly — rings never cross-contaminate."""
    from repro.dist import sharding as shardlib
    from repro.launch.mesh import make_host_mesh
    num_shards = 4
    mesh = make_host_mesh(data=num_shards, model=1)
    devices = shardlib.shard_devices(mesh, "data")
    snaps, values, max_edges = _trace()
    shard_streams = stream_sharded.encode_time_sliced(
        snaps, values, N, max_edges, BS, num_shards)
    want = [graphdiff.decode_stream(s, max_edges) for s in shard_streams]
    appliers = [DeltaApplier(max_edges, donate=donate, device=d)
                for d in devices]
    steps = len(shard_streams[0])
    for j in range(steps):
        outs = []
        for s in range(num_shards):
            item = stage_item(shard_streams[s][j], devices[s])
            e, m, _ = appliers[s].consume(item)
            outs.append((e, m))
        for s, (e, m) in enumerate(outs):
            assert list(e.devices()) == [devices[s]]
            we, wm = want[s][j]
            assert np.array_equal(np.asarray(e), we)
            assert np.array_equal(np.asarray(m), wm)


def test_slot_stacker_copies_survive_ring_donation():
    """SlotStacker.put must copy the ring buffers BEFORE the next consume
    donates them: after filling all slots, the block equals the decoded
    per-step sequence."""
    from repro.stream.prefetch import SlotStacker
    snaps, values, max_edges = _trace()
    stream = stream_encoder.encode_stream_fast(snaps, values, N, max_edges,
                                               BS)
    want = graphdiff.decode_stream(stream, max_edges)
    applier = DeltaApplier(max_edges)
    stacker = SlotStacker(len(stream))
    for j, item in enumerate(stream):
        e, m, v = applier.consume(stage_item(item))
        stacker.put(j, e, m, v)
    e_blk, m_blk, _ = stacker.arrays()
    for j, (we, wm) in enumerate(want):
        assert np.array_equal(np.asarray(e_blk[j]), we)
        assert np.array_equal(np.asarray(m_blk[j]), wm)


def test_delta_applier_reconstructs_stream():
    """Prefetched apply path (donated ring buffers) reproduces
    decode_stream's (edges, mask) sequence exactly."""
    snaps, values, max_edges = _trace()
    stream = stream_encoder.encode_stream_fast(snaps, values, N, max_edges,
                                               BS)
    want = graphdiff.decode_stream(stream, max_edges)
    applier = DeltaApplier(max_edges)
    for item, (we, wm) in zip(
            PrefetchIterator(iter(stream), stage_fn=stage_item, depth=2),
            want):
        e, m, _ = applier.consume(item)
        # copy out before the next consume donates these buffers
        assert np.array_equal(np.asarray(e), we)
        assert np.array_equal(np.asarray(m), wm)


@pytest.mark.parametrize("num_shards", [2, 4])
def test_sharded_streams_cover_time_slices(num_shards):
    """Each shard's self-contained stream decodes to exactly the snapshot
    edge sets of its owned steps (values aligned per edge)."""
    snaps, values, max_edges = _trace()
    shard_streams = stream_sharded.encode_time_sliced(
        snaps, values, N, max_edges, BS, num_shards)
    for s, stream in enumerate(shard_streams):
        steps = stream_sharded.shard_slice_steps(T, BS, num_shards, s)
        assert len(stream) == len(steps)
        decoded = graphdiff.decode_stream(stream, max_edges)
        for (e, m), t_global, item in zip(decoded, steps, stream):
            valid = e[m > 0]
            want = snaps[t_global]
            assert valid.shape == want.shape
            assert set(map(tuple, valid.tolist())) \
                == set(map(tuple, want.tolist()))
            # shipped values map to the right edges (valid lanes lead and
            # share the device ordering with the values array)
            key = {tuple(ed): float(v) for ed, v in
                   zip(want.tolist(), values[t_global])}
            for ed, v in zip(valid.tolist(),
                             item.values[:want.shape[0]]):
                assert key[tuple(ed)] == pytest.approx(float(v))
    total = sum(i.payload_bytes for st in shard_streams for i in st)
    assert total < num_shards * graphdiff.stream_bytes(
        stream_encoder.encode_stream_fast(snaps, values, N, max_edges, BS))


@pytest.mark.parametrize("model", ["tmgcn", "cdgcn", "evolvegcn"])
def test_prefetch_training_losses_bit_identical(model):
    """The overlapped transfer loop is a pure schedule change: per-step
    losses equal the synchronous path's exactly."""
    from repro.data.dyngnn import synthetic_dataset
    smooth = {"tmgcn": "mproduct", "evolvegcn": "edgelife",
              "cdgcn": "none"}[model]
    ds = synthetic_dataset(48, 8, density=2.0, churn=0.1,
                           smoothing_mode=smooth, window=3, seed=0)
    cfg = DynGNNConfig(model=model, num_nodes=48, num_steps=8, window=3,
                       checkpoint_blocks=2)
    frames, labels = np.asarray(ds.frames), np.asarray(ds.labels)
    sync = stream_train.train_streamed(
        cfg, ds.snapshots, ds.values, frames, labels, num_epochs=2,
        overlap=False)
    over = stream_train.train_streamed(
        cfg, ds.snapshots, ds.values, frames, labels, num_epochs=2,
        overlap=True, prefetch_depth=3)
    assert sync.losses == over.losses
    assert sync.losses[-1] < sync.losses[0] + 1e-6  # it actually trains
    import jax
    for a, b in zip(jax.tree.leaves(sync.params),
                    jax.tree.leaves(over.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("overlap", [False, True])
def test_streamed_loop_lets_each_item_go_after_its_step(overlap):
    """The loop keeps no staged item past the steps that follow it: a
    reference kept on the epoch's first item would hold its frame,
    labels and edge values on the device all epoch."""
    import weakref
    from repro.data.dyngnn import synthetic_dataset
    from repro.optim import adamw
    ds = synthetic_dataset(48, 8, density=2.0, churn=0.1,
                           smoothing_mode="mproduct", window=3, seed=0)
    cfg = DynGNNConfig(model="tmgcn", num_nodes=48, num_steps=8, window=3,
                       checkpoint_blocks=2)
    step = stream_train.make_stream_train_step(
        cfg, adamw.AdamWConfig(lr=1e-2, warmup_steps=1, total_steps=8))
    frames_seen, alive = [], []

    def recording_step(*args):
        frames_seen.append(weakref.ref(args[3]))
        alive.append(sum(r() is not None for r in frames_seen[:-3]))
        return step(*args)

    stream_train.train_streamed(
        cfg, ds.snapshots, ds.values, np.asarray(ds.frames),
        np.asarray(ds.labels), overlap=overlap, step_fn=recording_step)
    assert len(alive) == 8 and alive == [0] * 8


def test_pipeline_uses_stream_encoder_and_accounts_bytes():
    from repro.data.dyngnn import DTDGPipeline, synthetic_dataset
    ds = synthetic_dataset(64, 16, density=2.0, churn=0.1,
                           smoothing_mode="mproduct", window=3, seed=0)
    pipe = DTDGPipeline(ds, nb=2)
    rep = pipe.transfer_bytes()
    assert 0 < rep["graph_diff"] < rep["naive"]
    # lazy re-encode equals the eager stream
    lazy = list(pipe.host_stream())
    assert len(lazy) == ds.num_steps
    assert graphdiff.stream_bytes(lazy) == rep["graph_diff"]
    shards = pipe.sharded_streams(2)
    assert len(shards) == 2
