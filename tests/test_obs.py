"""repro.obs contract tests: tracer semantics (nesting, ring bounding,
thread safety, the disabled no-op, the profiler mirror), metrics
registry deltas, Perfetto export round-trip + schema validation, the
step's stage scopes in the compiled HLO, and the end-to-end traced fits
(per-snapshot spans on one device, per-round spans on the mesh that the
CI trace-smoke step gates on)."""

import json
import re
import threading

import numpy as np
import pytest

import jax

from repro import obs
from repro.core.models import DynGNNConfig
from repro.obs.trace import NULL_SPAN, Tracer
from repro.run import Engine, ExecutionPlan, RunConfig, SyntheticTrace

N, T, NB = 48, 16, 2


# --------------------------------------------------------------- tracer ----

def test_span_records_timing_and_attrs():
    trc = Tracer(enabled=True, fence=False)
    with trc.span("outer", round=3):
        with trc.span("inner", cat="sub"):
            pass
    spans = trc.spans()
    assert [s.name for s in spans] == ["inner", "outer"]  # exit order
    outer = spans[1]
    assert outer.attrs == {"round": 3}
    assert outer.dur_s >= spans[0].dur_s >= 0.0
    # containment on one thread: inner lies inside outer on the clock
    assert outer.start_s <= spans[0].start_s
    assert (outer.start_s + outer.dur_s
            >= spans[0].start_s + spans[0].dur_s)
    assert spans[0].tid == outer.tid == threading.get_ident()


def test_disabled_tracer_is_a_true_noop():
    trc = Tracer(enabled=False)
    sp = trc.span("anything", round=1)
    assert sp is NULL_SPAN              # shared object, no allocation
    with sp as s:
        assert s.fence("x") == "x"      # fence is identity
    assert trc.spans() == [] and trc.recorded == 0
    # the module-level helper takes the same fast path
    assert obs.span("x") is NULL_SPAN or obs.enabled()


def test_stopwatch_measures_even_when_disabled():
    trc = Tracer(enabled=False)
    with trc.stopwatch("work") as sw:
        sum(range(1000))
    assert sw.seconds > 0.0
    assert trc.spans() == []            # measured, but not recorded
    trc2 = Tracer(enabled=True, fence=False)
    with trc2.stopwatch("work", round=7) as sw2:
        pass
    (sp,) = trc2.spans()
    assert sp.name == "work" and sp.attrs == {"round": 7}
    assert sp.dur_s == sw2.seconds


def test_ring_bounds_and_counts_drops():
    trc = Tracer(enabled=True, capacity=8, fence=False)
    for i in range(22):
        with trc.span("s", i=i):
            pass
    assert len(trc.spans()) == 8
    assert trc.recorded == 22 and trc.dropped == 14
    # the ring keeps the newest spans
    assert [s.attrs["i"] for s in trc.spans()] == list(range(14, 22))


def test_tracer_thread_safety():
    trc = Tracer(enabled=True, capacity=10_000, fence=False)

    def worker(k):
        for i in range(100):
            with trc.span("t", k=k, i=i):
                pass

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert trc.recorded == 800 and trc.dropped == 0
    # all 8 workers' spans landed intact (tids may be reused by the OS)
    by_k = {k: 0 for k in range(8)}
    for s in trc.spans():
        by_k[s.attrs["k"]] += 1
    assert all(v == 100 for v in by_k.values())


def test_spans_since_checkpoint():
    trc = Tracer(enabled=True, fence=False)
    with trc.span("before"):
        pass
    mark = trc.recorded
    with trc.span("after"):
        pass
    assert [s.name for s in trc.spans_since(mark)] == ["after"]
    assert trc.summary(trc.spans_since(mark))["after"]["count"] == 1


# -------------------------------------------------------------- metrics ----

def test_metrics_inc_gauge_snapshot_delta():
    reg = obs.MetricsRegistry()
    reg.inc("a.count")
    reg.inc("a.count", 4)
    reg.gauge("b.level", 7.5)
    before = reg.snapshot()
    reg.inc("a.count", 2)
    reg.inc("c.new", 3)
    reg.gauge("b.level", 9.0)
    d = reg.delta(before)
    assert d["counters"] == {"a.count": 2, "c.new": 3}
    assert d["gauges"]["b.level"] == 9.0
    assert reg.get("a.count") == 7


def test_metrics_thread_safe_inc():
    reg = obs.MetricsRegistry()

    def worker():
        for _ in range(1000):
            reg.inc("n")

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.get("n") == 8000


def test_stream_report_mirrors_resync_counter():
    """Ad-hoc report counters and the obs registry stay in lockstep."""
    from repro.stream.encoder import ChurnOverflowError, StreamReport
    before = obs.metrics_snapshot()
    rep = StreamReport()
    rep.note_overflow(3, ChurnOverflowError(9, 2, 4, 4))
    d = obs.metrics().delta(before)
    assert d["counters"]["stream.resyncs"] == 1 == rep.resyncs


# --------------------------------------------------------------- export ----

@pytest.mark.parametrize("suffix", [".json", ".jsonl"])
def test_export_load_validate_roundtrip(tmp_path, suffix):
    trc = Tracer(enabled=True, fence=False)
    with trc.span("round", cat="round", round=0):
        with trc.span("round.transfer", round=0):
            pass
    path = tmp_path / f"trace{suffix}"
    obs.export_trace(path, tracer=trc,
                     metrics={"counters": {"stream.rounds": 1},
                              "gauges": {}})
    events, meta = obs.load_trace(path)
    assert obs.validate_trace(events) == []
    assert meta["format"] == "chrome-trace"
    assert meta["dropped_spans"] == 0
    by_ph = {}
    for ev in events:
        by_ph.setdefault(ev["ph"], []).append(ev)
    names = {ev["name"] for ev in by_ph["X"]}
    assert names == {"round", "round.transfer"}
    assert any(ev["name"] == "stream.rounds" for ev in by_ph["C"])
    assert any(ev["name"] == "thread_name" for ev in by_ph["M"])
    # timestamps are µs and the args carry the span attrs
    rnd = next(ev for ev in by_ph["X"] if ev["name"] == "round")
    assert rnd["args"]["round"] == 0 and rnd["dur"] >= 0


def test_validate_trace_catches_malformed_events(tmp_path):
    assert obs.validate_trace([]) == ["trace contains no events"]
    bad = [
        {"ph": "X", "ts": 0, "pid": 1, "tid": 1, "dur": 1},   # no name
        {"name": "a", "ph": "Z", "ts": 0, "pid": 1, "tid": 1},
        {"name": "b", "ph": "X", "ts": -5, "pid": 1, "tid": 1, "dur": 1},
        {"name": "c", "ph": "X", "ts": 0, "pid": 1, "tid": 1},  # no dur
        {"name": "d", "ph": "X", "ts": 0, "pid": 1, "tid": 1, "dur": 1,
         "args": "nope"},
    ]
    problems = obs.validate_trace(bad)
    assert len(problems) == 5
    # a hand-broken file fails through the same path the CI step runs
    p = tmp_path / "t.json"
    p.write_text(json.dumps({"traceEvents": bad}))
    events, _ = obs.load_trace(p)
    assert obs.validate_trace(events)


# ------------------------------------------------------------------ e2e ----

@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 host devices")
def test_traced_streamed_mesh_fit_exports_full_phase_coverage(tmp_path):
    """The acceptance path: a traced 4-shard fit yields the transfer,
    step and sync spans of every round, prefetch thread spans,
    RunResult.metrics, and a valid exported trace."""
    prev = obs.get_tracer()
    obs.configure(enabled=True)
    try:
        cfg = DynGNNConfig(model="tmgcn", num_nodes=N, num_steps=T,
                           window=3, checkpoint_blocks=NB)
        data = SyntheticTrace(num_nodes=N, num_steps=T, density=2.0,
                              churn=0.1, smoothing_mode="mproduct",
                              window=3)
        plan = ExecutionPlan(mode="streamed_mesh", shards=4, num_epochs=2)
        result = Engine(RunConfig(model=cfg, data=data, plan=plan)).fit()

        trc = obs.get_tracer()
        per_round: dict[int, set] = {}
        for sp in trc.spans():
            if "round" in sp.attrs:
                per_round.setdefault(sp.attrs["round"], set()).add(sp.name)
        rounds = sorted(per_round)
        assert rounds == list(range(2 * NB))
        for r in rounds:
            assert per_round[r] >= {"round", "round.transfer", "round.step",
                                    "round.sync"}, (r, per_round[r])
        names = {s.name for s in trc.spans()}
        assert {"prefetch.encode", "prefetch.stage", "prefetch.wait",
                "round.step"} <= names

        # session-scoped metrics landed on the result
        m = result.metrics
        assert m["counters"]["stream.rounds"] == 2 * NB
        assert m["counters"]["prefetch.items"] >= 2 * NB
        assert m["counters"]["stream.payload_bytes"] > 0
        assert m["spans"]["round"]["count"] == 2 * NB
        assert m["spans"]["round.sync"]["count"] == 2 * NB

        # and the whole thing survives the CI export -> check path
        path = tmp_path / "trace.json"
        obs.export_trace(path)
        events, _ = obs.load_trace(path)
        assert obs.validate_trace(events) == []
        from tools.check_trace import check
        assert check(str(path), ["prefetch.encode", "round.step"]) == []
    finally:
        obs.set_tracer(prev)


def test_untraced_fit_records_no_spans_but_still_counts():
    """Tracing off (the default): zero spans, async schedule untouched,
    but counters and RunResult.metrics still work."""
    assert not obs.enabled()
    trc = obs.get_tracer()
    before = trc.recorded
    cfg = DynGNNConfig(model="cdgcn", num_nodes=N, num_steps=T,
                       window=3, checkpoint_blocks=NB)
    data = SyntheticTrace(num_nodes=N, num_steps=T, density=2.0,
                          churn=0.1, smoothing_mode="none", window=3)
    plan = ExecutionPlan(mode="streamed", shards=1, num_epochs=1)
    result = Engine(RunConfig(model=cfg, data=data, plan=plan)).fit()
    assert trc.recorded == before           # no span escaped the no-op
    assert result.metrics is not None
    assert result.metrics["spans"] == {}
    assert np.isfinite(result.losses).all()


# ------------------------------------------------------ profiler mirror ----

class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs every enter
    and exit with the annotation's name and metadata."""

    def __init__(self):
        self.log: list[tuple] = []

    def __call__(self, name, **meta):
        log = self.log

        class _Ann:
            def __enter__(self):
                log.append(("enter", name, meta))
                return self

            def __exit__(self, *exc):
                log.append(("exit", name, meta))

        return _Ann()


@pytest.fixture
def annotations(monkeypatch):
    rec = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    return rec


def test_span_mirrors_a_trace_annotation_only_when_enabled(annotations):
    with Tracer(enabled=False).span("quiet", round=1):
        pass
    assert annotations.log == []
    with Tracer(enabled=True, fence=False).span("loud", cat="c", round=1):
        assert annotations.log == [("enter", "loud",
                                    {"cat": "c", "round": 1})]
    assert annotations.log[-1] == ("exit", "loud", {"cat": "c", "round": 1})


def test_stopwatch_mirrors_a_trace_annotation_only_when_enabled(
        annotations):
    with Tracer(enabled=False).stopwatch("quiet") as sw:
        pass
    assert sw.seconds >= 0 and annotations.log == []
    with Tracer(enabled=True, fence=False).stopwatch("loud", step=2):
        pass
    assert [(k, n) for k, n, _ in annotations.log] == [("enter", "loud"),
                                                       ("exit", "loud")]
    assert annotations.log[0][2] == {"cat": "phase", "step": 2}


def test_configure_refuses_derived_phases():
    prev = obs.get_tracer()
    try:
        # accepted from callers written before the derivation went away
        assert obs.configure(enabled=False, phases=False).enabled is False
        with pytest.raises(TypeError, match="phases"):
            obs.configure(enabled=True, phases=True)
    finally:
        obs.set_tracer(prev)


# --------------------------------------------------------- stage scopes ----

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^[\w.]+\((.*)\)$")


def _stages(op_name: str) -> list[str]:
    """The name-stack components of ``op_name`` that are stages, outer
    first, with jvp/transpose/... wrappers taken off."""
    out = []
    for part in op_name.split("/"):
        while (m := _WRAPPED.match(part)):
            part = m.group(1)
        if part in obs.STAGES:
            out.append(part)
    return out


def _op_names(hlo: str) -> list[str]:
    return _OP_NAME.findall(hlo)


@pytest.mark.parametrize("model,expected", [
    ("tmgcn", {"edge_weights", "spatial", "spmm", "temporal", "loss",
               "optimizer"}),
    ("cdgcn", {"edge_weights", "spatial", "spmm", "temporal", "loss",
               "optimizer"}),
    ("evolvegcn", {"edge_weights", "spatial", "spmm", "loss",
                   "optimizer"}),
])
def test_snapshot_step_ops_carry_their_stage(model, expected):
    """Every stage of the model names device ops of the compiled
    per-snapshot step, the backward ops of the spatial stage sit under
    ``transpose(jvp(spatial))``, and the sorted aggregation's own ops, in
    its forward and its custom backward rule, sit under ``spmm``."""
    import jax.numpy as jnp
    from repro.core import models as mdl
    from repro.optim import adamw
    from repro.stream import train_loop as tl
    n, e = 32, 64
    cfg = DynGNNConfig(model=model, num_nodes=n, num_steps=4, window=3)
    opt = adamw.AdamWConfig(lr=1e-2, warmup_steps=1, total_steps=4)
    params = mdl.init_params(jax.random.PRNGKey(0), cfg)
    step = tl.make_stream_train_step(cfg, opt)
    hlo = step.lower(
        params, adamw.init_state(params), mdl.init_carries(cfg, params),
        jnp.zeros((n, cfg.feat_in)), jnp.zeros((e, 2), jnp.int32),
        jnp.ones((e,)), jnp.ones((e,)), jnp.zeros((n,), jnp.int32),
        jnp.int32(0)).compile().as_text()
    names = _op_names(hlo)
    seen = {st for name in names for st in _stages(name)}
    assert expected <= seen, expected - seen
    assert any("transpose(jvp(spatial))" in name for name in names)
    if model != "evolvegcn":
        assert not {"a2a", "delta_apply"} & seen
    # the sorted aggregation: its lane sort (and the sort's comparator)
    # and the reduction behind its platform switch, in the forward and in
    # the custom backward rule, all sit under spmm (names outside jit(step)
    # belong to sub-computations, such as the sort's comparator, not ops)
    agg = [n for n in names if n.startswith("jit(") and (
        n.rsplit("/", 1)[-1] in ("sort", "lt_to") or "/cond/" in n)]
    assert any("transpose(" in n for n in agg), agg
    assert any("transpose(" not in n for n in agg), agg
    for name in agg:
        assert _stages(name)[-1:] == ["spmm"], name
        assert "edge_weights" not in _stages(name), name


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 host devices")
@pytest.mark.parametrize("compression", ["none", "int8_a2a"])
def test_every_mesh_all_to_all_is_under_a2a(compression):
    from repro.launch.mesh import make_host_mesh
    from repro.stream.distributed import lowered_step_hlo
    cfg = DynGNNConfig(model="tmgcn", num_nodes=32, num_steps=8, window=3,
                       checkpoint_blocks=2)
    hlo = lowered_step_hlo(cfg, make_host_mesh(data=4, model=1), win=4,
                           max_edges=64, compression=compression)
    a2a = [line for line in hlo.splitlines() if " all-to-all(" in line]
    assert a2a, "the mesh step lost its all-to-alls"
    for line in a2a:
        (name,) = _OP_NAME.findall(line)
        assert _stages(name)[-1:] == ["a2a"], line


def test_apply_delta_ops_are_under_delta_apply():
    import jax.numpy as jnp
    from repro.core import graphdiff
    e = 16
    hlo = jax.jit(graphdiff.apply_delta).lower(
        jnp.zeros((e, 2), jnp.int32), jnp.ones((e,)),
        jnp.zeros((4,), jnp.int32), jnp.ones((4,)),
        jnp.zeros((4, 2), jnp.int32), jnp.ones((4,))).compile().as_text()
    names = [n for n in _op_names(hlo) if "/" in n]
    assert names and all(_stages(n) == ["delta_apply"] for n in names)


def test_traced_streamed_fit_records_per_snapshot_spans():
    """One device, per-snapshot schedule: each snapshot records its
    apply, step and sync spans and one ``stream.steps`` count; the
    prefetch worker records one encode per item plus the pull that
    finds the stream's end; each epoch records one ``stream.epoch_start``."""
    prev = obs.get_tracer()
    obs.configure(enabled=True, fence=False)
    try:
        cfg = DynGNNConfig(model="tmgcn", num_nodes=N, num_steps=T,
                           window=3, checkpoint_blocks=NB)
        data = SyntheticTrace(num_nodes=N, num_steps=T, density=2.0,
                              churn=0.1, smoothing_mode="mproduct",
                              window=3)
        plan = ExecutionPlan(mode="streamed", shards=1, num_epochs=1)
        result = Engine(RunConfig(model=cfg, data=data, plan=plan,
                                  log_fn=lambda s: None)).fit()
        spans = result.metrics["spans"]
        for name in ("stream.apply", "stream.step", "stream.sync"):
            assert spans[name]["count"] == T, name
        assert spans["prefetch.encode"]["count"] == T + 1
        assert spans["stream.epoch_start"]["count"] == 1
        assert result.metrics["counters"]["stream.steps"] == T
        steps = [s.attrs for s in obs.get_tracer().spans()
                 if s.name == "stream.step"]
        assert steps == [{"epoch": 0, "step": t} for t in range(T)]
    finally:
        obs.set_tracer(prev)
