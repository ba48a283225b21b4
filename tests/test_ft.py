"""Fault-tolerance suite: checkpoint roundtrip + atomicity, elastic
re-mesh + re-blocking, preemption, straggler watchdog, gradient
compression."""

import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.checkpoint import Checkpointer
from repro.dist import compression
from repro.ft import elastic, straggler


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"a": jax.random.normal(k, (4, 8)),
            "nested": {"b": jnp.arange(6, dtype=jnp.float32),
                       "c": [jnp.ones((2,)), jnp.zeros((3,))]}}


def test_checkpoint_roundtrip(tmp_path):
    ck = Checkpointer(tmp_path)
    tree = _tree()
    ck.save(10, tree, extra={"cursor": 123}, blocking=True)
    restored, extra = ck.restore(10, tree)
    assert extra["cursor"] == 123
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_checkpoint_async_and_retention(tmp_path):
    ck = Checkpointer(tmp_path, keep=2)
    tree = _tree()
    for s in (1, 2, 3, 4):
        ck.save(s, tree)
    ck.wait()
    assert ck.all_steps() == [3, 4]


def test_checkpoint_structure_mismatch_detected(tmp_path):
    ck = Checkpointer(tmp_path)
    ck.save(1, _tree(), blocking=True)
    bad = {"a": jnp.zeros((4, 8))}
    with pytest.raises(ValueError, match="incompatible"):
        ck.restore(1, bad)


def test_checkpoint_restore_onto_mesh(tmp_path):
    """Elastic scaling: save host-gathered, restore sharded on a mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(data=4, model=2)
    ck = Checkpointer(tmp_path)
    tree = {"w": jnp.arange(32, dtype=jnp.float32).reshape(8, 4)}
    ck.save(5, tree, blocking=True)
    sh = {"w": NamedSharding(mesh, P("data", "model"))}
    restored, _ = ck.restore(5, tree, shardings=sh)
    assert restored["w"].sharding == sh["w"]
    np.testing.assert_allclose(np.asarray(restored["w"]),
                               np.asarray(tree["w"]))


def test_remesh_plan():
    assert elastic.remesh_plan(512, 16) == elastic.MeshPlan(32, 16)
    assert elastic.remesh_plan(256, 16) == elastic.MeshPlan(16, 16)
    # losing a node: 248 chips don't divide by 16 -> fall back to 8
    plan = elastic.remesh_plan(248, 16)
    assert plan.chips == 248 and 248 % plan.model == 0


def test_dyngnn_elastic_blocks():
    nb, bsize = elastic.dyngnn_elastic_blocks(256, 16, target_bsize=64)
    assert 256 % nb == 0 and bsize % 16 == 0 and bsize <= 64
    nb2, bsize2 = elastic.dyngnn_elastic_blocks(256, 32, target_bsize=64)
    assert bsize2 % 32 == 0


@pytest.mark.parametrize("t,p", [(10, 3), (7, 2), (100, 16)])
def test_dyngnn_elastic_blocks_always_tiles_or_raises(t, p):
    """Regression: the old fallback returned (T//P, P) even when P does
    not divide T — an illegal blocking with nb*bsize != T.  Now every
    return tiles the timeline exactly, and the untileable case raises."""
    if t % p:
        with pytest.raises(ValueError, match="cannot be tiled"):
            elastic.dyngnn_elastic_blocks(t, p, target_bsize=4)
    else:
        nb, bsize = elastic.dyngnn_elastic_blocks(t, p, target_bsize=4)
        assert nb * bsize == t and bsize % p == 0
    with pytest.raises(ValueError, match=">= 1"):
        elastic.dyngnn_elastic_blocks(0, 1, target_bsize=4)
    with pytest.raises(ValueError, match=">= 1"):
        elastic.dyngnn_elastic_blocks(8, 0, target_bsize=4)


def test_preemption_guard():
    with elastic.PreemptionGuard() as g:
        assert not g.preempted
        os.kill(os.getpid(), signal.SIGTERM)
        assert g.preempted   # handler flips the flag instead of killing us


def test_preemption_guard_chains_previous_handler():
    """An already-installed SIGTERM handler still runs (the guard chains,
    never clobbers), and __exit__ restores it exactly."""
    calls = []

    def prev(signum, frame):
        calls.append(signum)

    before = signal.signal(signal.SIGTERM, prev)
    try:
        with elastic.PreemptionGuard() as g:
            os.kill(os.getpid(), signal.SIGTERM)
            assert g.preempted
            assert calls == [signal.SIGTERM]      # chained through
        assert signal.getsignal(signal.SIGTERM) is prev
    finally:
        signal.signal(signal.SIGTERM, before)


def test_preemption_guard_nested_guards_restore_in_order():
    """Nested guards: the inner handler chains to the outer one (both
    flags flip on one signal) and each __exit__ restores the handler it
    replaced — LIFO unwind leaves the process handler untouched."""
    base = signal.getsignal(signal.SIGTERM)
    with elastic.PreemptionGuard() as outer:
        mid = signal.getsignal(signal.SIGTERM)
        with elastic.PreemptionGuard() as inner:
            os.kill(os.getpid(), signal.SIGTERM)
            assert inner.preempted and outer.preempted
        assert signal.getsignal(signal.SIGTERM) is mid
    assert signal.getsignal(signal.SIGTERM) is base


def test_preemption_guard_sigint_opt_in():
    """catch_sigint=True converts SIGINT into the flag (no
    KeyboardInterrupt); the default guard leaves SIGINT alone."""
    default_int = signal.getsignal(signal.SIGINT)
    with elastic.PreemptionGuard(catch_sigint=True) as g:
        os.kill(os.getpid(), signal.SIGINT)   # would raise if unhandled
        assert g.preempted
    assert signal.getsignal(signal.SIGINT) is default_int
    with elastic.PreemptionGuard() as g2:
        assert signal.getsignal(signal.SIGINT) is default_int
        assert not g2.preempted


def test_straggler_timer_flags_outliers():
    t = straggler.StepTimer(window=50, threshold_std=3.0)
    for _ in range(30):
        t.observe(0.1 + np.random.default_rng(0).normal() * 1e-4)
    assert t.observe(1.0) is True
    assert t.straggler_rate > 0


def test_straggler_timer_ewma_recurrence():
    """The smoothed moments follow the documented EW update exactly:
    mean += alpha*diff; var = (1-alpha)*(var + alpha*diff^2)."""
    t = straggler.StepTimer(window=9, threshold_std=3.0, min_steps=3)
    assert t.alpha == pytest.approx(2.0 / 10.0)
    seq = [0.10, 0.12, 0.08, 0.11, 0.30, 0.10]
    mean, var = seq[0], 0.0
    t.observe(seq[0])
    for dt in seq[1:]:
        t.observe(dt)
        diff = dt - mean
        mean += t.alpha * diff
        var = (1 - t.alpha) * (var + t.alpha * diff * diff)
    assert t.mean == pytest.approx(mean)
    assert t.var == pytest.approx(var)
    assert t.step_idx == len(seq)
    assert list(t.times) == seq


def test_straggler_timer_outlier_cannot_mask_itself():
    """The flag check runs BEFORE the EWMA update, so a huge step is
    judged against the pre-outlier estimate."""
    t = straggler.StepTimer(window=50, threshold_std=3.0, min_steps=5)
    for _ in range(20):
        t.observe(0.1)
    assert t.observe(5.0) is True
    assert t.flagged_steps == [21]


def test_straggler_timer_reset():
    t = straggler.StepTimer(window=10, min_steps=2)
    for _ in range(8):
        t.observe(0.2)
    t.observe(9.0)
    t.reset()
    assert t.mean == 0.0 and t.var == 0.0 and t.step_idx == 0
    assert not t.times and not t.flagged_steps
    # post-reset the estimate re-seeds from scratch: a step that would
    # have been flagged against the old mean passes quietly
    assert t.observe(9.0) is False
    assert t.mean == pytest.approx(9.0)


def test_straggler_timer_counts_flags_in_obs():
    from repro import obs
    before = obs.metrics_snapshot()
    t = straggler.StepTimer(window=50, threshold_std=3.0, min_steps=5)
    for _ in range(20):
        t.observe(0.1)
    t.observe(5.0)
    d = obs.metrics().delta(before)
    assert d["counters"]["straggler.flags"] == 1


def test_step_timer_wired_through_distributed_stream():
    """An injected StepTimer observes every distributed round."""
    from repro.core.models import DynGNNConfig
    from repro.data.dyngnn import synthetic_dataset
    from repro.launch.mesh import make_host_mesh
    from repro.stream import distributed as dist
    n, t_steps, nb = 48, 16, 2
    ds = synthetic_dataset(n, t_steps, density=2.0, churn=0.1,
                           smoothing_mode="mproduct", window=3, seed=0)
    cfg = DynGNNConfig(model="tmgcn", num_nodes=n, num_steps=t_steps,
                       window=3, checkpoint_blocks=nb)
    timer = straggler.StepTimer(window=8)
    st = dist.train_distributed_streamed(
        cfg, ds.snapshots, ds.values, np.asarray(ds.frames),
        np.asarray(ds.labels), mesh=make_host_mesh(data=4, model=1),
        num_epochs=2, step_timer=timer)
    assert st.step_timer is timer
    assert timer.step_idx == len(st.losses) == 2 * nb
    assert len(timer.times) == 2 * nb


def test_backup_shard_schedule():
    sched = straggler.BackupShardSchedule(num_workers=8, num_backups=2)
    times = [0.1] * 8
    times[3], times[5] = 0.9, 0.8
    plan = sched.plan(times)
    assert set(plan.keys()) == {3, 5}
    # backup shard cursor identical to the primary's (O(1) reassignment)
    assert sched.shard_for(3, 4) == (12, 4)


def test_int8_error_feedback_compression():
    """Compressed psum matches exact psum within quantization error, and
    error feedback drives the residual to track the truncation."""
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(data=4, model=1)
    rng = np.random.default_rng(0)
    g_local = jnp.asarray(rng.normal(size=(4, 16)).astype(np.float32))

    def fn(g):
        grads = {"w": g[0]}
        res = compression.init_residual(grads)
        red, new_res = compression.compressed_psum(grads, "data", res)
        return red["w"], new_res["w"]

    out, res = jax.jit(shard_map(
        fn, mesh=mesh, in_specs=(P("data", None),),
        out_specs=(P(), P()), check_vma=False))(g_local)
    exact = np.asarray(g_local).mean(axis=0)
    np.testing.assert_allclose(np.asarray(out), exact, atol=0.05)
    # residual bounded by one quantization bucket
    assert float(jnp.max(jnp.abs(res))) < 0.05
