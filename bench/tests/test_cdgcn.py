"""CD-GCN: the program's streamed step against the plain reference
(``bench/models/cdgcn.py``) on the CPU at a tiny size, the reference's
work counts by hand, and a fault of the recurrent carry that the cell's
check has to refuse."""

import dataclasses
import json

import jax
import numpy as np
import pytest

import cell as cell_mod
import check
import generate
import reference
import stages
import trace_reduce
import work
from test_stages import NS, PLAIN, SCOPED, _profile, _reader
from tiny import ROOT, make_root, run_cell

WORKLOAD = "cdgcn-epinions-1chip"
SEED = 2**31 + 37
cdgcn = reference.model("cdgcn")

# The CPU computes every product in float32 with no rounding of its
# inputs, so program and reference differ only in the order of their
# sums: the aggregation's sorted segment sum against the reference's
# scatter-add, XLA's fusion of the LSTM's gates.  On four seeds at N=256
# the largest gaps were 1.7e-7 of a loss, 5.0e-8 of the largest gradient
# element and 5.1e-6 of the largest change (an element of ``wx`` whose
# small gradient Adam scales up to a whole step, its rounding with it).
# The tolerances leave room of about ten over those.  The cell's limits
# (1e-5, 1e-4, 5e-5, on bench/check.py's relative gaps) come from the
# chip's readings, and a reset carry must break them.
LOSS_RTOL = 2e-6
GRAD_TOL = 1e-6
CHANGE_TOL = 5e-5


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(scope="module")
def tiny_cell(root):
    return cell_mod.load_cell(WORKLOAD, root)


@pytest.fixture(scope="module")
def readings(tiny_cell):
    """(program: losses, first clipped gradient, change; reference at
    ``highest``) over one epoch of the tiny cell, as a run compares
    them."""
    jax.clear_caches()
    raw = generate.trace_for(tiny_cell.config, tiny_cell.traffic, SEED)
    eng = cell_mod.build_program(tiny_cell, raw, SEED)
    rr = eng.resolve()
    eng.fit()
    key = cell_mod.STEP_CACHE_KEY[tiny_cell.config["plan"]["mode"]]
    rec = cell_mod.StepRecorder(rr.cache[key], cell_mod.kept_steps(tiny_cell))
    rr.cache[key] = rec
    res = eng.fit()
    grad, change = cell_mod.program_readings(rec, tiny_cell)
    ref = cell_mod.reference_readings(
        tiny_cell, cell_mod.reference_slices(tiny_cell, raw), SEED,
        precision="highest")
    return res.losses, grad, change, ref


def test_the_tiny_cell_is_cdgcn_on_raw_snapshots(tiny_cell):
    c = tiny_cell.config
    assert (c["model"], c["smoothing"], c["plan"]["mode"]) \
        == ("cdgcn", "none", "streamed")
    assert (c["num_nodes"], c["smoothed_edges_per_snapshot"]) == (256, 700)
    assert tiny_cell.steps_per_epoch == 16 and tiny_cell.slice_len == 1


def test_losses_of_every_step_agree(readings):
    losses, _, _, ref = readings
    assert len(losses) == len(ref.losses) == 16
    np.testing.assert_allclose(losses, ref.losses, rtol=LOSS_RTOL)
    # the carried state moves the loss: the steps differ from each other
    assert np.ptp(losses) > 1e-3


def _leaves(tree):
    return {jax.tree_util.keystr(k): np.asarray(v)
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.mark.parametrize("which,tol", [("grad", GRAD_TOL),
                                       ("change", CHANGE_TOL)])
def test_every_leaf_agrees(readings, which, tol):
    _, grad, change, ref = readings
    prog, want = ((grad, ref.first_grad) if which == "grad"
                  else (change, ref.change))
    p, r = _leaves(prog), _leaves(want)
    assert set(p) == set(r) and len(r) == 12
    scale = max(float(np.abs(v).max()) for v in r.values())
    for k in r:
        np.testing.assert_allclose(p[k], r[k], rtol=0, atol=tol * scale,
                                   err_msg=k)


def test_the_recurrent_weight_has_no_first_gradient(readings):
    """h starts at zero, so the first update cannot move ``wh``: the
    check's gradient and change gaps cannot see the carry, and only the
    losses of the later steps can."""
    _, grad, _, ref = readings
    for g in (grad, ref.first_grad):
        assert all(float(np.abs(lp["lstm"]["wh"]).max()) == 0.0
                   for lp in g["layers"])
    norms = check.leaf_norms(ref.first_grad)
    med = float(np.median(list(norms.values())))
    assert norms["layers[0].lstm.wh"] < check.STILL * med


def test_init_params_are_the_programs(tiny_cell):
    from repro.core import models as mdl

    c = tiny_cell.config
    cfg = mdl.DynGNNConfig(
        model="cdgcn", num_nodes=c["num_nodes"], feat_in=c["feat_in"],
        hidden=c["hidden"], out_dim=c["out_dim"],
        num_layers=c["num_layers"], num_classes=c["num_classes"])
    prog = cdgcn.from_program(mdl.init_params(jax.random.PRNGKey(SEED), cfg))
    mine = cdgcn.init_params(SEED, c)
    assert jax.tree.structure(prog) == jax.tree.structure(mine)
    for a, b in zip(jax.tree.leaves(prog), jax.tree.leaves(mine),
                    strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert mine["layers"][0]["lstm"]["wx"].shape == (2 + 6, 24)
    assert mine["layers"][1]["lstm"]["wx"].shape == (6 + 6, 24)


def test_zero_carries():
    params = cdgcn.init_params(1, {"feat_in": 2, "hidden": 6, "out_dim": 6,
                                   "num_layers": 2, "num_classes": 2})
    carries = cdgcn.zero_carries(params, 10, {})
    assert [tuple(x.shape for x in hc) for hc in carries] \
        == [((10, 6), (10, 6))] * 2


def test_no_exchange_is_refused():
    with pytest.raises(ValueError, match="several chips"):
        cdgcn.slice_loss(None, [], np.zeros((1, 4, 2)), None, None, None, 0,
                         cfg={}, dtype=np.float32, fault="no_exchange")


LSTM_READERS = ["train.lstm_ms", "train.lstm_roofline"]


def _context(path):
    """The CD-GCN cell over a hand-built trace of two chips and two
    snapshots (``test_stages``' fixtures)."""
    cell = dataclasses.replace(cell_mod.load_cell(WORKLOAD), chips=2)
    ctx = cell_mod.Context(
        cell=cell, peaks={"bf16_flops_per_s": 197e12,
                          "hbm_bytes_per_s": 819e9},
        setup={}, window={"snapshots": 2, "seconds": 1.0, "epochs": 2},
        spans={}, transfer_bytes_per_epoch=0.0,
        trace=trace_reduce.reduce(_profile(path), chips=2, epochs=2,
                                  rounds=2))
    ctx.stages = stages.reduce(stages.from_text(path.read_text()), chips=2)
    return ctx


def test_lstm_readers_on_a_scoped_trace():
    read = {name: _reader(name)(_context(SCOPED)) for name in LSTM_READERS}
    seconds = 500 * NS / 2                   # under temporal, per snapshot
    assert read["train.lstm_ms"] == pytest.approx(1e3 * seconds)
    least = cdgcn.lstm_per_snapshot(cell_mod.shape_of(
        cell_mod.load_cell(WORKLOAD)))
    assert read["train.lstm_roofline"] == pytest.approx(
        100 * max(least["flops"] / 197e12, least["bytes"] / 819e9)
        / seconds)


@pytest.mark.parametrize("name", LSTM_READERS)
def test_lstm_readers_read_nothing_without_scopes_or_trace(name):
    assert _reader(name)(_context(PLAIN)) is None
    ctx = _context(SCOPED)
    del ctx.stages
    ctx.trace = None
    assert _reader(name)(ctx) is None


def test_the_lstm_metrics_are_declared_for_the_cdgcn_cell_alone():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    declared = {m["name"]: m for m in manifest["per_layer"]}
    for name in LSTM_READERS:
        m = declared[name]
        assert (m["layer"], m["source"], m["moves"], m["workloads"]) == (
            "step", "device_trace", "train_snapshots_per_s", [WORKLOAD])


# the tests' tiny shape: 4 vertices, 3 edges (7 lanes with self-loops),
# widths 1 -> 2 -> 2, so LSTM inputs a = 1 + 2 and 2 + 2, H = 2
TINY = work.Shape(num_nodes=4, num_edges=3, feat_in=1, hidden=2, out_dim=2,
                  num_layers=2, window=2, num_classes=2)


def test_lstm_work_by_hand():
    n, h = 4, 2
    fwd = bwd = nbytes = 0
    for a in (3, 4):
        fwd += 2 * n * (a + h) * 4 * h + 13 * n * h
        bwd += 2 * n * (a + h) * 4 * h + 2 * n * 4 * h * a + 24 * n * h
        nbytes += 2 * 4 * n * (a + 4 * h)
    got = cdgcn.lstm_per_snapshot(TINY)
    assert (got["forward"], got["backward"]) == (fwd, bwd) == (912, 1536)
    assert got["flops"] == 2448 and got["bytes"] == nbytes == 736


def test_flops_by_hand():
    weights = 2 * 7 + 2 * 4 + 2 * 7
    layer0 = 2 * 7 * 1 + (2 * 4 * 1 * 2 + 4 * 2 + 4 * 3)
    layer1 = 2 * 7 * 2 + (2 * 4 * 2 * 2 + 4 * 2 + 4 * 4)
    head = 2 * 4 * 2 * 2 + 4 * 2 + 4 * 4 * 2 + 4
    back0 = 4 * 3 + (2 * 4 * 1 * 2 + 4 * 2)
    back1 = 4 * 4 + (2 * 4 * 2 * 2 + 4 * 2) + (2 * 4 * 2 * 2 + 4 * 2) \
        + 2 * 7 * 2
    back_head = 4 * 4 * 2 + 2 * 4 * 2 * 2 + 4 * 2 + 2 * 4 * 2 * 2
    got = cdgcn.flops_per_snapshot(TINY)
    assert got["forward"] == weights + layer0 + layer1 + head + 912 == 1158
    assert got["backward"] == back0 + back1 + back_head + 1536 == 1800
    assert got["total"] == 2958


def test_bytes_by_hand():
    edge = 12
    weights = 2 * edge * 7 + 4 * 7
    agg0 = edge * 7 + 4 * 1 * 7 + 4 * 4 * 1
    agg1 = edge * 7 + 4 * 2 * 7 + 4 * 4 * 2
    dense0, dense1 = 4 * 4 * (1 + 3), 4 * 4 * (2 + 4)
    logits = 4 * 4 * (2 + 2)
    got = cdgcn.bytes_per_snapshot(TINY)
    assert got["forward"] == (weights + agg0 + dense0 + agg1 + dense1
                              + logits + 368) == 1088
    assert got["backward"] == dense0 + dense1 + agg1 + logits + 368 == 764


def test_aggregation_work_is_the_shared_count():
    assert cdgcn.spmm_per_snapshot is work.spmm_per_snapshot
    assert cdgcn.spmm_per_snapshot(TINY) == {"flops": 70, "bytes": 472}


def test_full_size_shape_counts_raw_edges():
    s = cell_mod.shape_of(cell_mod.load_cell(WORKLOAD))
    assert (s.num_nodes, s.num_edges, s.lanes) \
        == (755_200, 2_097_152, 2_852_352)
    lstm = cdgcn.lstm_per_snapshot(s)
    # bytes bound: the LSTM's least time is its HBM traffic
    assert lstm["bytes"] / 819e9 > 10 * lstm["flops"] / 197e12


def _carry_reset(mp):
    """The LSTM starts every snapshot from zero: the carried (h, c) is
    dropped."""
    from repro.core import temporal
    orig = temporal.lstm_scan
    mp.setattr(temporal, "lstm_scan",
               lambda params, x, init_state=None: orig(params, x))


def test_a_reset_carry_is_not_correct(root, monkeypatch):
    _carry_reset(monkeypatch)
    jax.clear_caches()
    out = run_cell(root, WORKLOAD, SEED)
    assert out["correct"] is False
    assert out["check"]["loss_gap"]["value"] \
        > out["check"]["loss_gap"]["limit"], out["check"]
