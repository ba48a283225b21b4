"""The trace reduction on a small trace built by hand in the layout of a
TPU trace (``fixtures/trace_2chips.pbtxt``, times in ns)."""

import pytest
from jax.profiler import ProfileData

import trace_reduce
from tiny import BENCH

FIXTURE = BENCH / "tests" / "fixtures" / "trace_2chips.pbtxt"
NS = 1e-9


def _profile(text=None):
    return ProfileData.from_text_proto(text or FIXTURE.read_text())


def test_busy_idle_step_and_exposed_all_to_all():
    red = trace_reduce.reduce(_profile(), chips=2, epochs=2, rounds=2)
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
    red = trace_reduce.reduce(_profile(), chips=2, epochs=2, rounds=2)
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
    red = trace_reduce.reduce(_profile(), chips=1, epochs=2, rounds=2)
    assert red.busy_s == pytest.approx(7_600 * NS)
    assert red.step_device_s == pytest.approx(7_600 * NS)


def test_unknown_step_module_fails_the_reduction():
    """A module on the step path that the lists do not name (a renamed or
    fused step, say) must fail the run, not yield a share of part of the
    step's time."""
    text = FIXTURE.read_text().replace('"jit_copy(4)"', '"jit_fused(4)"')
    with pytest.raises(trace_reduce.UnknownModule, match="jit_fused"):
        trace_reduce.reduce(_profile(text), chips=2, epochs=2, rounds=2)


def test_epoch_module_counts_before_the_first_step_only():
    """The parameters' re-initialisation runs before an epoch's first
    train step and counts with the step time, also where the device's
    clock puts it before the host's mark of its epoch (chip 0's second
    draw, at 6500, starts 300 before the mark at 6800); the same module
    between two train steps of one epoch fails the run (step work moved
    where it would not be named).  With one step to an epoch, as in the
    fixture, every gap between steps is an epoch boundary: a misplaced
    draw there cannot be told from the next epoch's own, so the case
    that must fail runs two steps to an epoch."""
    before = FIXTURE.read_text().replace(
        '"jit_apply_delta(3)"', '"jit__uniform(3)"').replace(
        '"jit_copy(4)"', '"jit__threefry_seed(4)"')
    red = trace_reduce.reduce(_profile(before), chips=2, epochs=2, rounds=2)
    assert red.step_device_s == pytest.approx((7_600 + 6_000) * NS)
    assert red.modules["jit__uniform"] == pytest.approx(400 * NS)
    assert red.modules["jit__threefry_seed"] == pytest.approx(200 * NS)
    after = _epochs({0: [0, 0], 1: [0, 0]}, between=(0, 0, "jit_sqrt"))
    with pytest.raises(trace_reduce.UnknownModule, match="epoch module"):
        trace_reduce.reduce(_profile(after), chips=2, epochs=2, rounds=4)


US = 1_000                                  # ns
EPOCH = 10_000 * US


def _epochs(skews, steps=2, *, drop=None, between=None,
            draw="jit__threefry_seed"):
    """Text of a trace of ``len(skews[c])`` epochs on chips ``skews``:
    each epoch's mark spans 10 ms, and on chip ``c`` its parameter draw
    (``draw`` and ``jit__uniform``, 20 us each) starts ``skews[c][k]`` ns
    before the mark, its delta-apply 1 ms and its ``steps`` train steps
    (2 ms each, 3 ms apart) 2 ms after it.  ``drop=(c, k)`` leaves out
    the last train step of epoch k on chip c; ``between=(c, k, name)``
    runs module ``name`` between its first two."""
    names = [draw, "jit__uniform", "jit_apply_delta", "jit_step"]
    if between:
        names.append(between[2])
    meta = "".join(f'  event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                   f'name: "{n}({i + 1})" }} }}\n'
                   f'  event_metadata {{ key: {i + 11} value {{ id: {i + 11} '
                   f'name: "op.{i + 1}" }} }}\n'
                   for i, n in enumerate(names))
    planes = []
    for c, per_epoch in sorted(skews.items()):
        events = []
        for k, skew in enumerate(per_epoch):
            mark = US + k * EPOCH
            events += [(1, mark - skew, 20 * US),
                       (2, mark - skew + 20 * US, 20 * US),
                       (3, mark + 1_000 * US, 500 * US)]
            n = steps - (drop == (c, k))
            events += [(4, mark + 2_000 * US + j * 3_000 * US, 2_000 * US)
                       for j in range(n)]
            if between and between[:2] == (c, k):
                events.append((5, mark + 4_200 * US, 10 * US))
        lines = "".join(
            f'  lines {{ id: {ln + 1} name: "{name}" timestamp_ns: 0\n'
            + "".join(f"    events {{ metadata_id: {m + 10 * ln} "
                      f"offset_ps: {a * 1000} duration_ps: {d * 1000} }}\n"
                      for m, a, d in events) + "  }\n"
            for ln, name in enumerate(("XLA Modules", "XLA Ops")))
        planes.append(f'planes {{ id: {c + 1} name: "/device:TPU:{c}"\n'
                      f"{lines}{meta}}}\n")
    epochs = len(next(iter(skews.values())))
    marks = "".join(f"    events {{ metadata_id: 1 offset_ps: "
                    f"{(US + k * EPOCH) * 1000} duration_ps: "
                    f"{EPOCH * 1000} }}\n" for k in range(epochs))
    planes.append('planes { id: 9 name: "/host:CPU"\n'
                  '  lines { id: 1 name: "python3" timestamp_ns: 0\n'
                  f"{marks}  }}\n"
                  '  event_metadata { key: 1 value { id: 1 '
                  'name: "bench.epoch" } }\n}\n')
    return "".join(planes)


def test_epochs_by_their_order_on_each_chip_not_by_the_marks():
    """Three epochs whose draws start 20 us, 0.5 ms and 0.9 ms before
    their marks on chip 0 and at them on chip 1 (the device's clock
    reads ahead of the host's, more as the window goes on) reduce to the
    numbers of the trace without the skew; only the first draw's 20 us,
    which fall before the window opens, are not in it."""
    plain = trace_reduce.reduce(_profile(_epochs({0: [0, 0, 0],
                                                  1: [0, 0, 0]})),
                                chips=2, epochs=3, rounds=6)
    skewed = trace_reduce.reduce(
        _profile(_epochs({0: [20 * US, 500 * US, 900 * US], 1: [0, 0, 0]})),
        chips=2, epochs=3, rounds=6)
    early = 20 * US * NS
    assert skewed.window_s == pytest.approx(plain.window_s)
    assert skewed.modules == pytest.approx(
        {**plain.modules,
         "jit__threefry_seed": plain.modules["jit__threefry_seed"] - early})
    assert skewed.step_device_s == pytest.approx(plain.step_device_s - early)
    assert skewed.busy_s == pytest.approx(plain.busy_s - early / 2)
    assert skewed.a2a_exposed_s == plain.a2a_exposed_s == 0
    assert plain.modules["jit_step"] == pytest.approx(12 * 2_000 * US * NS)
    assert plain.step_device_s == pytest.approx(
        2 * 3 * (40 + 500 + 4_000) * US * NS)


def test_an_epoch_module_between_two_steps_of_one_epoch_fails():
    with pytest.raises(trace_reduce.UnknownModule, match="epoch module"):
        trace_reduce.reduce(_profile(_epochs(
            {0: [0, 0, 0], 1: [0, 0, 0]}, between=(1, 1, "jit_sqrt"))),
            chips=2, epochs=3, rounds=6)


def test_a_train_step_too_few_on_one_chip_fails():
    with pytest.raises(trace_reduce.UnknownModule, match="5 train steps"):
        trace_reduce.reduce(_profile(_epochs(
            {0: [0, 0, 0], 1: [0, 0, 0]}, drop=(1, 2))),
            chips=2, epochs=3, rounds=6)


def test_a_key_folded_in_before_the_first_step_is_an_epoch_module():
    """EvolveGCN's parameter draw folds a key in."""
    red = trace_reduce.reduce(_profile(_epochs(
        {0: [0, 300 * US], 1: [0, 0]}, draw="jit__threefry_fold_in")),
        chips=2, epochs=2, rounds=4)
    assert red.modules["jit__threefry_fold_in"] == pytest.approx(
        4 * 20 * US * NS)


def test_a_chip_that_draws_no_parameters_is_held_to_its_steps():
    """On the mesh the parameters are drawn on one chip; the others run
    the window's train steps and nothing else."""
    chip0, chip1 = _epochs({0: [0, 0], 1: [0, 0]}).split("/device:TPU:1")
    chip1 = chip1.replace('"jit__threefry_seed(1)"', '"jit_copy(1)"').replace(
        '"jit__uniform(2)"', '"jit_copy(2)"')
    red = trace_reduce.reduce(_profile(f"{chip0}/device:TPU:1{chip1}"),
                              chips=2, epochs=2, rounds=4)
    assert red.modules["jit_copy"] == pytest.approx(2 * 40 * US * NS)
    assert red.modules["jit__uniform"] == pytest.approx(2 * 20 * US * NS)


def test_reduce_dir_reads_the_xplane_file(tmp_path):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(FIXTURE.read_text()))
    red = trace_reduce.reduce_dir(str(tmp_path), chips=2, epochs=2, rounds=2)
    assert red.busy_s == pytest.approx(6_800 * NS)


def test_missing_window_or_planes_is_an_error():
    text = FIXTURE.read_text().replace('"bench.epoch"', '"other"')
    with pytest.raises(RuntimeError, match="bench.epoch"):
        trace_reduce.reduce(_profile(text), chips=2, epochs=2, rounds=2)
    with pytest.raises(RuntimeError, match="TPU planes"):
        trace_reduce.reduce(_profile(), chips=4, epochs=2, rounds=2)


def test_a_window_whose_epochs_disagree_with_its_marks_is_an_error():
    """The fixture's window holds two ``bench.epoch`` marks."""
    for epochs in (1, 3):
        with pytest.raises(RuntimeError, match="2 'bench.epoch' marks"):
            trace_reduce.reduce(_profile(), chips=2, epochs=epochs,
                                rounds=2)


def test_union_and_gaps():
    assert trace_reduce.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace_reduce.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4),
                                                         (5, 6)]
