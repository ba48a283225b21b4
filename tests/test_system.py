"""End-to-end behaviour tests for the paper's system: full training runs
with convergence, checkpoint-resume, sampler integration, and
link-prediction evaluation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import models
from repro.data.dyngnn import DTDGPipeline, synthetic_dataset
from repro.launch.mesh import make_host_mesh
from repro.optim import adamw
from repro.train import trainer


def _pipe(model="tmgcn", n=64, t=16, nb=2):
    smoothing_mode = {"tmgcn": "mproduct", "evolvegcn": "edgelife",
                      "cdgcn": "none"}[model]
    ds = synthetic_dataset(n, t, density=2.0, churn=0.1,
                           smoothing_mode=smoothing_mode, window=3, seed=0)
    return ds, DTDGPipeline(ds, nb=nb)


@pytest.mark.parametrize("model", ["tmgcn", "cdgcn", "evolvegcn"])
def test_training_reduces_loss_single_device(model):
    ds, pipe = _pipe(model)
    cfg = models.DynGNNConfig(model=model, num_nodes=64, num_steps=16,
                              window=3, checkpoint_blocks=2)
    from repro.optim import adamw
    opt = adamw.AdamWConfig(lr=3e-2, warmup_steps=5, total_steps=60,
                            weight_decay=0.0)
    state, losses = trainer.train_dyngnn(cfg, pipe, mesh=None, num_steps=60,
                                         opt_cfg=opt, log_fn=lambda *_: None)
    assert losses[-1] < losses[0] - 0.05, losses[:3] + losses[-3:]


def test_training_distributed_matches_single(tmp_path):
    """Same seed, same data: distributed loss curve == single-device curve
    (paper Fig. 6, as an exact test)."""
    ds, pipe = _pipe("tmgcn")
    cfg = models.DynGNNConfig(model="tmgcn", num_nodes=64, num_steps=16,
                              window=3, checkpoint_blocks=2)
    mesh = make_host_mesh(data=4, model=1)
    _, losses_sp = trainer.train_dyngnn(cfg, pipe, mesh=mesh, num_steps=10,
                                        log_fn=lambda *_: None)
    _, losses_1d = trainer.train_dyngnn(cfg, pipe, mesh=None, num_steps=10,
                                        log_fn=lambda *_: None)
    np.testing.assert_allclose(losses_sp, losses_1d, atol=1e-4)


def test_checkpoint_resume(tmp_path):
    ds, pipe = _pipe("cdgcn")
    cfg = models.DynGNNConfig(model="cdgcn", num_nodes=64, num_steps=16,
                              window=3, checkpoint_blocks=2)
    d = str(tmp_path / "ck")
    state1, _ = trainer.train_dyngnn(cfg, pipe, num_steps=10, ckpt_dir=d,
                                     ckpt_every=5, log_fn=lambda *_: None)
    # "crash" and resume: a fresh call picks up at step 10
    state2, losses2 = trainer.train_dyngnn(cfg, pipe, num_steps=15,
                                           ckpt_dir=d, ckpt_every=5,
                                           log_fn=lambda *_: None)
    assert state2.step == 15
    assert len(losses2) == 5   # only steps 10..14 re-run


def test_link_prediction_evaluation():
    ds, pipe = _pipe("tmgcn", n=64, t=16)
    cfg = models.DynGNNConfig(model="tmgcn", num_nodes=64, num_steps=16,
                              window=3, checkpoint_blocks=2)
    state, _ = trainer.train_dyngnn(cfg, pipe, num_steps=20,
                                    log_fn=lambda *_: None)
    test_snap = ds.snapshots[-1]
    acc = trainer.evaluate_link_prediction(cfg, state.params, pipe,
                                           test_snap)
    assert 0.0 <= acc <= 1.0


def test_neighbor_sampler_produces_valid_subgraphs():
    from repro.graph import generate
    from repro.graph.sampler import CSRGraph, sample_neighbors, flat_edges
    rng = np.random.default_rng(0)
    n = 500
    edges = generate.random_static_graph(n, 5000, seed=0)
    g = CSRGraph.from_edges(edges, n)
    seeds = rng.choice(n, 32, replace=False)
    sub = sample_neighbors(g, seeds, fanouts=[5, 3],
                           rng=np.random.default_rng(1))
    assert sub.num_seeds == 32
    e, m = flat_edges(sub)
    valid = e[m > 0]
    # all local ids within the sampled node table
    n_valid = int(sub.node_mask.sum())
    assert valid.max() < n_valid
    # every sampled edge exists in the original graph (global ids)
    gsrc = sub.node_ids[valid[:, 0]]
    gdst = sub.node_ids[valid[:, 1]]
    edge_set = set(map(tuple, edges.tolist()))
    assert all((int(s), int(d)) in edge_set for s, d in zip(gsrc, gdst))
    # fanout bound respected
    assert valid.shape[0] <= 32 * 5 + 32 * 5 * 3


def test_dtdg_pipeline_transfer_accounting():
    ds, pipe = _pipe("tmgcn")
    rep = pipe.transfer_bytes()
    assert 0 < rep["graph_diff"] < rep["naive"]


def test_grad_compression_trains():
    """int8 error-feedback DP aggregation still converges (EvolveGCN's only
    communication path, §5.5 + compression)."""
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.dist import compression
    mesh = make_host_mesh(data=4, model=1)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 4)).astype(np.float32))
    w_true = jnp.asarray([[1.0], [-2.0], [0.5], [3.0]])
    y = x @ w_true

    def local_step(w, res, xb, yb):
        g = jax.grad(lambda w_: jnp.mean((xb @ w_ - yb) ** 2))(w)
        red, res = compression.compressed_psum({"w": g}, "data", {"w": res})
        return w - 0.1 * red["w"], res["w"]

    fn = jax.jit(shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), P(), P("data", None), P("data", None)),
        out_specs=(P(), P()), check_vma=False))
    w = jnp.zeros((4, 1))
    res = jnp.zeros((4, 1))
    for _ in range(150):
        w, res = fn(w, res, x, y)
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_true), atol=0.1)
