"""The stage and span reduction (``stages.py``) on a trace built by hand
in the layout of a v5e trace of a scoped program
(``fixtures/trace_2chips_scoped.pbtxt``, times in ns), its readers, and
the spatial aggregation's work count."""

import dataclasses
import importlib.util
import json

import pytest

import cell as cell_mod
import stages
import trace_reduce
import work
from tiny import BENCH

SCOPED = BENCH / "tests" / "fixtures" / "trace_2chips_scoped.pbtxt"
PLAIN = BENCH / "tests" / "fixtures" / "trace_2chips.pbtxt"
NS = 1e-9
NEW_READERS = ["train.spatial_ms", "train.spmm_roofline",
               "train.temporal_ms", "train.edge_weights_ms",
               "train.delta_apply_ms", "train.update_ms",
               "train.unscoped_ms", "host.encode_ms",
               "device.idle_attributed_pct", "train.a2a_ms"]
MANIFEST = json.loads((BENCH.parent / "BENCHMARK.json").read_text())


def _reduce(path=SCOPED):
    return stages.reduce(stages.from_text(path.read_text()), chips=2)


@pytest.mark.parametrize("op_name,expected", [
    ("jit(step)/jvp(spatial)/vmap(spmm)/scatter-add:", ("spmm", "fwd")),
    ("jit(step)/transpose(jvp(spatial))/vmap(spmm)/gather:",
     ("spmm", "bwd")),
    ("jit(step)/transpose(jvp(spatial))/vmap()/dot_general:",
     ("spatial", "bwd")),
    ("jit(step)/jvp(shard_map)/transpose(jvp(a2a))/all-to-all",
     ("a2a", "bwd")),
    ("jit(step)/optimizer/jit(clip)/max:", ("optimizer", "fwd")),
    ("jit(apply_delta)/delta_apply/scatter:", ("delta_apply", "fwd")),
    ("jit(step)/jvp()/iota:", ("unscoped", "fwd")),
    ("x:", ("unscoped", "fwd")),
    ("", ("unscoped", "fwd")),
])
def test_stage_of_takes_the_innermost_stage_and_the_pass(op_name, expected):
    assert stages.stage_of(op_name) == expected


def test_ops_go_to_their_innermost_stage_and_partition_the_op_time():
    red = _reduce()
    assert red.window_s == pytest.approx(10_000 * NS)      # 1000..11000
    assert red.stage_s == pytest.approx({
        ("temporal", "fwd"): 500 * NS,       # clipped to the window
        ("spmm", "fwd"): 6_000 * NS,         # 2000 on chip 0, 4000 on 1
        ("spmm", "bwd"): 1_000 * NS,
        ("spatial", "bwd"): 500 * NS,
        ("unscoped", "fwd"): 100 * NS,       # a copy with no op_name
        ("delta_apply", "fwd"): 300 * NS,    # op_name on the event itself
        ("optimizer", "fwd"): 400 * NS,      # op_name as a stat reference
        ("a2a", "fwd"): 1_000 * NS})
    assert red.scoped
    # stages plus unscoped are every op in the window; here that is also
    # every module the window runs, as the step time reads it
    assert red.op_s == pytest.approx(sum(red.stage_s.values()))
    assert red.op_s == pytest.approx(trace_reduce.reduce(
        _profile(SCOPED), chips=2, epochs=2, rounds=2).step_device_s)
    assert red.seconds("spatial", "spmm") == pytest.approx(7_500 * NS)
    assert red.device_ops[0] == ["spmm/fwd · fusion.1",
                                 pytest.approx(6_000 * NS)]
    assert all(" · " in name for name, _ in red.device_ops)


def test_an_op_without_tf_op_takes_its_op_name_from_the_program_hlo():
    """A v5e leaves ``tf_op`` off the scatter fusions; their ``op_name``
    then comes from the program's HLO proto on the metadata plane: the
    fusion's own (none here), else its fused computation's root's (a bare
    ``scatter-add`` is no name stack), else the last inner instruction
    that has one."""
    space = stages.from_text(SCOPED.read_text())
    hlo = stages.message_class("HloProto")()
    entry, fused = hlo.hlo_module.computations.add(), \
        hlo.hlo_module.computations.add()
    entry.id, entry.root_id, fused.id, fused.root_id = 1, 10, 2, 20
    fusion = entry.instructions.add()
    fusion.name, fusion.id = "fusion.4", 10
    fusion.called_computation_ids.append(2)
    for name, iid, op_name in (
            ("scatter.1", 20, "scatter-add"),
            ("mul.1", 21, "jit(step)/transpose(jvp(spatial))/vmap(spmm)/mul"),
            ("iota.1", 22, "")):
        ins = fused.instructions.add()
        ins.name, ins.id, ins.metadata.op_name = name, iid, op_name
    meta = space.planes.add()
    meta.name = stages.METADATA_PLANE
    meta.stat_metadata.add(key=1).value.name = stages.HLO_PROTO_STAT
    md = meta.event_metadata.add(key=5).value
    md.name = "jit_step(77)"
    md.stats.add(metadata_id=1, bytes_value=hlo.SerializeToString())
    chip0 = space.planes[0]
    chip0.stat_metadata.add(key=9).value.name = stages.PROGRAM_STAT
    copy = next(e.value for e in chip0.event_metadata if e.key == 14)
    copy.display_name = "fusion.4"
    copy.stats.add(metadata_id=9, uint64_value=77)
    red = stages.reduce(space, chips=2)
    assert ("unscoped", "fwd") not in red.stage_s
    assert red.stage_s[("spmm", "bwd")] == pytest.approx(1_100 * NS)


def test_program_spans_in_the_window():
    red = _reduce()
    assert red.spans == pytest.approx({
        "stream.epoch_start": 1_900 * NS, "prefetch.wait": 700 * NS,
        "stream.step": 100 * NS, "stream.sync": 4_000 * NS,
        "prefetch.encode": 1_100 * NS})       # 500..1800 clipped at 1000
    assert "PjitFunction(step)" not in red.spans      # JAX's, no 'cat'
    assert "bench.epoch" not in red.spans


def test_idle_gaps_are_named_by_the_program_spans_open_over_them():
    red = _reduce()
    # chip 0 idles 1500..2000, 5600..6500, 6800..7000 and 7400..11000
    assert red.idle_s == pytest.approx(5_200 * NS)
    # under program spans: 500 + (100 + 500) + 100 + 0
    assert red.idle_attributed_s == pytest.approx(1_200 * NS)
    assert [g[0] for g in red.gaps] == [
        "python3: np.asarray(jax.Array)",     # no program span open
        "stream.epoch_start + prefetch.encode",
        "stream.epoch_start + prefetch.encode",   # wait is shorter overlap
        "stream.epoch_start"]
    assert [g[1] for g in red.gaps] == pytest.approx(
        [3_600 * NS, 900 * NS, 500 * NS, 200 * NS])


def test_reduce_dir_reads_the_xplane_file(tmp_path):
    from jax.profiler import ProfileData
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(SCOPED.read_text()))
    red = stages.reduce_dir(str(tmp_path), chips=1)
    assert red.op_s == pytest.approx(4_800 * NS)       # chip 0 alone


def _profile(path):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(path.read_text())


def _reader(name):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "test_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _context(path, snapshots=2):
    # the fixture's two chips stand in for a mesh cell's
    cell = dataclasses.replace(cell_mod.load_cell("tmgcn-epinions-1chip"),
                               chips=2)
    ctx = cell_mod.Context(
        cell=cell, peaks={"bf16_flops_per_s": 197e12,
                          "hbm_bytes_per_s": 819e9},
        setup={}, window={"snapshots": snapshots, "seconds": 1.0,
                          "epochs": 2},
        spans={}, transfer_bytes_per_epoch=0.0,
        trace=trace_reduce.reduce(_profile(path), chips=2, epochs=2,
                                  rounds=2))
    ctx.stages = stages.reduce(stages.from_text(path.read_text()), chips=2)
    return ctx


def test_readers_on_a_scoped_trace():
    ctx = _context(SCOPED)
    read = {name: _reader(name)(ctx) for name in NEW_READERS}
    ms = 1e3 * NS / 2                        # ms per snapshot of 1 ns
    assert read["train.spatial_ms"] == pytest.approx(7_500 * ms)
    assert read["train.temporal_ms"] == pytest.approx(500 * ms)
    assert read["train.edge_weights_ms"] == 0.0
    assert read["train.delta_apply_ms"] == pytest.approx(300 * ms)
    assert read["train.update_ms"] == pytest.approx(400 * ms)
    assert read["train.unscoped_ms"] == pytest.approx(100 * ms)
    assert read["host.encode_ms"] == pytest.approx(1_100 * ms)
    assert read["device.idle_attributed_pct"] == pytest.approx(
        100 * 1_200 / 5_200)
    # a2a: 1000 ns over 2 chips and 2 rounds, the exposed reader's base
    assert read["train.a2a_ms"] == pytest.approx(1e3 * 1_000 * NS / 4)
    least = work.spmm_per_snapshot(cell_mod.shape_of(ctx.cell))
    assert read["train.spmm_roofline"] == pytest.approx(
        100 * max(least["flops"] / 197e12, least["bytes"] / 819e9)
        / (7_000 * NS / 2))
    # the stage readers sum to the op time per snapshot, exactly
    stage_sum = sum(read[n] for n in (
        "train.spatial_ms", "train.temporal_ms", "train.edge_weights_ms",
        "train.delta_apply_ms", "train.update_ms", "train.unscoped_ms"))
    a2a = 1e3 * ctx.stages.seconds("a2a") / 2         # not per round
    assert stage_sum + a2a == pytest.approx(1e3 * ctx.stages.op_s / 2)


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_read_nothing_from_a_program_without_scopes(name):
    """The parent's program names no stage and mirrors no span: each new
    reader returns None there, not zero."""
    assert _reader(name)(_context(PLAIN)) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_read_nothing_untraced(name):
    ctx = _context(SCOPED)
    del ctx.stages
    ctx.trace = None
    assert _reader(name)(ctx) is None


def test_the_trace_is_found_in_the_harness_frame(tmp_path, monkeypatch):
    """``cell.run`` passes its readers no path to the trace; ``of`` finds
    ``trace_dir`` in that frame and reduces the trace once per Context."""
    from jax.profiler import ProfileData
    d = tmp_path / "trace" / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(SCOPED.read_text()))
    harness = tmp_path / "cell.py"
    harness.write_text(
        "def run(ctx, trace_dir, read):\n    return read(ctx)\n")
    spec = importlib.util.spec_from_file_location("harness_cell", harness)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    ctx = _context(SCOPED)
    del ctx.stages
    calls = []
    reduce_dir = stages.reduce_dir

    def counting(path, chips):
        calls.append(path)
        return reduce_dir(path, chips=chips)

    monkeypatch.setattr(stages, "reduce_dir", counting)
    trace_dir = str(tmp_path / "trace")
    a = mod.run(ctx, trace_dir, _reader("train.spatial_ms"))
    b = mod.run(ctx, trace_dir, _reader("train.update_ms"))
    assert a == pytest.approx(1e3 * 7_500 * NS / 2)
    assert b == pytest.approx(1e3 * 400 * NS / 2)
    assert calls == [trace_dir]                         # reduced once
    other = _context(SCOPED)
    del other.stages
    assert stages.of(other) is None                     # no such frame


def test_spmm_per_snapshot_against_a_hand_count():
    # 10 vertices, 20 edges -> 30 lanes; widths 2 -> 3 -> 4: passes are
    # layer 1 forward (d=2), layer 2 forward and transpose (d=3, d=3)
    s = work.Shape(num_nodes=10, num_edges=20, feat_in=2, hidden=3,
                   out_dim=4, num_layers=2, window=5, num_classes=2)
    got = work.spmm_per_snapshot(s)
    assert got["flops"] == 2 * 30 * (2 + 3 + 3)
    # per pass: 12 B of edge list and weight per lane, 4*d B gathered per
    # lane, 4*d B per vertex written
    assert got["bytes"] == sum(12 * 30 + 4 * d * 30 + 4 * 10 * d
                               for d in (2, 3, 3))


def test_spmm_work_at_the_epinions_shape():
    s = cell_mod.shape_of(cell_mod.load_cell("tmgcn-epinions-1chip"))
    got = work.spmm_per_snapshot(s)
    assert got["flops"] == 79_865_912                   # 79.9 MFLOP
    assert got["bytes"] == 304_707_768                  # 304.7 MB
    assert got["bytes"] / 819e9 == pytest.approx(0.372e-3, rel=1e-3)
    # the aggregation is part of the step's counted work, never more
    assert got["flops"] < work.flops_per_snapshot(s)["total"]
    assert got["bytes"] < work.bytes_per_snapshot(s)["total"]


def test_every_new_metric_is_declared_for_the_cells_it_reads():
    """The one-chip cell reports every new metric; the all-to-all readers
    wait for a cell on four chips (none is declared yet)."""
    declared = {m["name"]: m for m in MANIFEST["per_layer"]}
    chips = {w["name"]: w["chips"] for w in MANIFEST["workloads"]}
    for name in NEW_READERS:
        if name.startswith("train.a2a"):
            assert name not in declared or all(
                chips[w] == 4 for w in declared[name]["workloads"])
            continue
        m = declared[name]
        assert m["moves"] == "train_snapshots_per_s"
        assert m["workloads"] == ["tmgcn-epinions-1chip"]
