"""The trace reduction on a small trace built by hand in the layout of a
TPU trace (``fixtures/trace_2chips.pbtxt``, times in ns)."""

import pytest
from jax.profiler import ProfileData

import trace_reduce
from conftest import BENCH

FIXTURE = BENCH / "tests" / "fixtures" / "trace_2chips.pbtxt"
NS = 1e-9


def _profile(text=None):
    return ProfileData.from_text_proto(text or FIXTURE.read_text())


def test_busy_idle_step_and_exposed_all_to_all():
    red = trace_reduce.reduce(_profile(), chips=2, rounds=2)
    assert red.window_s == pytest.approx(10_000 * NS)       # 1000..11000
    # chip 0: 400 + 4000 + 200 + 3000; chip 1: 6000 (ops after the
    # window's end are left out)
    assert red.busy_s == pytest.approx((7_600 + 6_000) / 2 * NS)
    # every step-path module in the window, both chips
    assert red.step_device_s == pytest.approx((7_600 + 6_000) * NS)
    assert red.modules == pytest.approx({
        "jit_apply_delta": 400 * NS, "jit_step": 13_000 * NS,
        "jit_copy": 200 * NS})
    # chip 0: 3500..5000 overlaps compute until 4000 -> 1000 exposed,
    # 9000..10000 wholly exposed; chip 1's all-to-all is hidden
    assert red.a2a_exposed_s == pytest.approx(2_000 * NS)


def test_breakdown():
    red = trace_reduce.reduce(_profile(), chips=2, rounds=2)
    ops = dict(red.breakdown["device_ops"])
    assert list(ops)[0] == "fusion.1"
    assert ops["fusion.1"] == pytest.approx(10_000 * NS)
    assert ops["all-to-all.2"] == pytest.approx(3_500 * NS)
    assert "fusion.9" not in ops                        # after the window
    gaps = red.breakdown["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx(
        [1_000 * NS, 500 * NS, 500 * NS, 300 * NS, 100 * NS])
    names = [g[0] for g in gaps]
    assert names[0] == "python3: PjitFunction(init)"
    assert "python3: PjitFunction(apply_delta)" in names
    assert "prefetch: TransferToDevice" in names        # 6700..7000
    assert "python3: PjitFunction(step)" in names       # 1900..2000
    assert len(gaps) <= 10 and len(red.breakdown["device_ops"]) <= 10


def test_one_chip_of_two():
    red = trace_reduce.reduce(_profile(), chips=1, rounds=2)
    assert red.busy_s == pytest.approx(7_600 * NS)
    assert red.step_device_s == pytest.approx(7_600 * NS)


def test_unknown_step_module_fails_the_reduction():
    """A module on the step path that the lists do not name (a renamed or
    fused step, say) must fail the run, not yield a share of part of the
    step's time."""
    text = FIXTURE.read_text().replace('"jit_copy(4)"', '"jit_fused(4)"')
    with pytest.raises(trace_reduce.UnknownModule, match="jit_fused"):
        trace_reduce.reduce(_profile(text), chips=2, rounds=2)


def test_epoch_module_counts_before_the_first_step_only():
    """The parameters' re-initialisation runs before an epoch's first
    train step and counts with the step time; the same module after that
    step fails the run (step work moved where it would not be named)."""
    before = FIXTURE.read_text().replace('"jit_apply_delta(3)"',
                                         '"jit__uniform(3)"')
    red = trace_reduce.reduce(_profile(before), chips=2, rounds=2)
    assert red.step_device_s == pytest.approx((7_600 + 6_000) * NS)
    assert red.modules["jit__uniform"] == pytest.approx(400 * NS)
    after = FIXTURE.read_text().replace('"jit_copy(4)"', '"jit_sqrt(4)"')
    with pytest.raises(trace_reduce.UnknownModule, match="jit_sqrt"):
        trace_reduce.reduce(_profile(after), chips=2, rounds=2)


def test_reduce_dir_reads_the_xplane_file(tmp_path):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(FIXTURE.read_text()))
    red = trace_reduce.reduce_dir(str(tmp_path), chips=2, rounds=2)
    assert red.busy_s == pytest.approx(6_800 * NS)


def test_missing_window_or_planes_is_an_error():
    text = FIXTURE.read_text().replace('"bench.epoch"', '"other"')
    with pytest.raises(RuntimeError, match="bench.epoch"):
        trace_reduce.reduce(_profile(text), chips=2, rounds=2)
    with pytest.raises(RuntimeError, match="TPU planes"):
        trace_reduce.reduce(_profile(), chips=4, rounds=2)


def test_union_and_gaps():
    assert trace_reduce.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace_reduce.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4),
                                                         (5, 6)]
