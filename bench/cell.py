"""One benchmark cell, run once: set-up, the measured window, the check.

Everything specific to a configuration, a traffic mix or a per-layer
metric lives in a data file or reader of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json`` — the model, graph shape, plan,
  optimizer and the correctness limits;
* ``bench/traffic/<traffic>.json`` — the generator's parameters;
* ``bench/metrics/<metric>.py`` — a reader ``read(ctx)`` that returns the
  metric, or None where it finds nothing to read.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

import check
import generate
import reference
import work

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent
GIB = 2 ** 30
# updates whose optimizer state is kept: the first gradient, and the
# change over these; the reference follows the whole epoch's losses
KEPT_STEPS = 3
# the program's compiled step, as its Engine caches it, per schedule
STEP_CACHE_KEY = {"streamed": "stream_step", "streamed_mesh": "dist_step"}


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    @property
    def steps_per_epoch(self) -> int:
        """Optimizer updates in one epoch: one per snapshot on one chip,
        one per round of a checkpoint block on the mesh."""
        c = self.config
        if c["plan"]["mode"] == "streamed":
            return c["num_steps"]
        return c["checkpoint_blocks"]

    @property
    def slice_len(self) -> int:
        return self.config["num_steps"] // self.steps_per_epoch


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload`` in ``root/BENCHMARK.json``.  Exits,
    naming what is missing, where this tree lacks a file the cell needs:
    its configuration, its traffic, its reference model or a reader of
    one of its per-layer metrics."""
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    w = cells[workload]
    entry = {c["name"]: c for c in manifest["configs"]}[w["config"]]

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    per_layer = [m for m in manifest["per_layer"] if mine(m)]
    config = root / entry["file"]
    traffic = HERE / "traffic" / f"{w['traffic']}.json"
    needed = [config, traffic] + [HERE / "metrics" / f"{m['name']}.py"
                                  for m in per_layer]
    if config.exists():
        model = json.loads(config.read_text())["model"]
        needed.append(reference.MODELS / f"{model}.py")
    missing = [str(p.relative_to(ROOT) if p.is_relative_to(ROOT) else p)
               for p in needed if not p.exists()]
    if missing:
        raise SystemExit(f"workload {workload!r} cannot run on this tree: "
                         f"missing {', '.join(missing)}")
    return Cell(
        name=workload, chips=w["chips"],
        config=json.loads(config.read_text()),
        traffic=json.loads(traffic.read_text()),
        end_to_end=[m for m in manifest["end_to_end"] if mine(m)],
        per_layer=per_layer)


def load_reader(metric: str):
    """``read`` of ``bench/metrics/<metric>.py``."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileClock:
    """Backend compiles (count and seconds) and persistent-cache hits,
    from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


class StepRecorder:
    """Pass-through around the program's compiled step: keeps the
    parameters of its first call and the optimizer state that its first
    ``keep`` calls return.  Nothing is copied or synchronised."""

    def __init__(self, step, keep: int):
        self.step = step
        self.keep = keep
        self.first_params = None
        self.states: list = []

    def __call__(self, params, opt_state, *rest):
        out = self.step(params, opt_state, *rest)
        if self.first_params is None:
            self.first_params = params
        if len(self.states) < self.keep:
            self.states.append(out[1])
        return out


def _quiet(_msg: str) -> None:
    pass


def build_program(cell: Cell, raw: list[np.ndarray], seed: int):
    """The program under test, resolved: an ``Engine`` over the cell's
    trace (smoothing, features, labels and encoding are the program's)."""
    import jax.numpy as jnp
    from repro.core.models import DynGNNConfig
    from repro.data.dyngnn import dataset_from_snapshots
    from repro.optim.adamw import AdamWConfig
    from repro.run import Engine, ExecutionPlan, InMemoryDTDG, RunConfig

    c = cell.config
    ds = dataset_from_snapshots(raw, c["num_nodes"],
                                smoothing_mode=c["smoothing"],
                                window=c["window"])
    model = DynGNNConfig(
        model=c["model"], num_nodes=c["num_nodes"],
        num_steps=c["num_steps"], feat_in=c["feat_in"], hidden=c["hidden"],
        out_dim=c["out_dim"], num_layers=c["num_layers"],
        window=c["window"], num_classes=c["num_classes"],
        checkpoint_blocks=c["checkpoint_blocks"],
        param_dtype=jnp.dtype(c["dtype"]))
    plan = ExecutionPlan(mode=c["plan"]["mode"], shards=c["plan"]["shards"],
                         num_epochs=1)
    eng = Engine(RunConfig(model=model, data=InMemoryDTDG(ds), plan=plan,
                           optimizer=AdamWConfig(**c["optimizer"]),
                           seed=seed, log_every=1 << 30, log_fn=_quiet))
    eng.resolve()
    return eng


def reference_slices(cell: Cell, raw) -> list[reference.Slice]:
    """The reference's own inputs for one epoch, one slice per update."""
    c = cell.config
    return reference.build_slices(raw, c["num_nodes"], c["window"],
                                  cell.slice_len, cell.steps_per_epoch,
                                  c["smoothing"])


def kept_steps(cell: Cell) -> int:
    return min(KEPT_STEPS, cell.steps_per_epoch)


def reference_readings(cell: Cell, slices, seed: int, *, dtype=None,
                       precision: str | None = None,
                       fault: str | None = None) -> reference.Readings:
    """The reference (or, with ``dtype``/``fault``, the control or a
    planted fault) trained over one epoch of ``slices``."""
    import jax.numpy as jnp
    c = cell.config
    mod = reference.model(c["model"])
    params = mod.init_params(seed, c)
    o = c["optimizer"]
    opt = reference.AdamW(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                          weight_decay=o["weight_decay"],
                          grad_clip=o["grad_clip"],
                          warmup_steps=o["warmup_steps"],
                          total_steps=o["total_steps"],
                          min_lr_frac=o["min_lr_frac"])
    return reference.train(mod, c, params, slices, opt,
                           change_after=kept_steps(cell),
                           dtype=dtype or jnp.float32, precision=precision,
                           groups=c["plan"]["shards"], fault=fault)


def program_readings(rec: StepRecorder, cell: Cell):
    """(first clipped gradient, change over the kept steps) of the
    program, from the optimizer state its step returned: Adam's first
    moment after one update is (1 - b1) times the clipped gradient."""
    import jax
    beta1 = cell.config["optimizer"]["b1"]
    to_ref = reference.model(cell.config["model"]).from_program
    first = jax.device_get(rec.states[0])
    last = jax.device_get(rec.states[-1])
    p0 = jax.device_get(rec.first_params)
    grad = jax.tree.map(lambda m: np.asarray(m) / (1 - beta1), first["m"])
    change = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                          last["master"], p0)
    return to_ref(grad), to_ref(change)


def shape_of(cell: Cell) -> work.Shape:
    c = cell.config
    m = generate.raw_edges_per_snapshot(c["smoothed_edges_per_snapshot"],
                                        cell.traffic["churn"], c["smoothing"],
                                        c["window"])
    union = m + (min(c["window"], c["num_steps"]) - 1) * round(
        cell.traffic["churn"] * m) if c["smoothing"] != "none" else m
    return work.Shape(num_nodes=c["num_nodes"], num_edges=union,
                      feat_in=c["feat_in"], hidden=c["hidden"],
                      out_dim=c["out_dim"], num_layers=c["num_layers"],
                      window=c["window"], num_classes=c["num_classes"])


@dataclass
class Context:
    """What the per-layer readers read."""
    cell: Cell
    peaks: dict
    setup: dict
    window: dict
    spans: dict
    transfer_bytes_per_epoch: float
    trace: object = None                # trace_reduce.Reduction or None
    work: dict = field(default_factory=dict)


def run(cell: Cell, seed: int, seconds: float, traced: bool, *,
        t_start: float, peaks: dict, devices, trace_dir: str | None = None,
        log=print) -> dict:
    """Run the cell once and return the result object (the last line)."""
    import jax
    from repro import obs

    clock = CompileClock()
    c = cell.config

    # ---- set-up: trace, program, warm-up epoch (compiles or loads) ----
    raw = generate.trace_for(c, cell.traffic, seed)
    t0 = time.perf_counter()
    eng = build_program(cell, raw, seed)
    host_s = time.perf_counter() - t0
    rr = eng.resolve()
    eng.fit()
    key = STEP_CACHE_KEY[c["plan"]["mode"]]
    rec = StepRecorder(rr.cache[key], kept_steps(cell))
    rr.cache[key] = rec
    setup = {"host_s": host_s, "compile_s": clock.seconds,
             "compiles": clock.count, "cache_hits": clock.hits}
    if traced:
        obs.configure(enabled=True, fence=False, phases=False)
    setup["total_s"] = time.perf_counter() - t_start
    log(f"set-up {setup!r}")

    # ---- the measured window: whole epochs, back to back ----
    compiles0 = clock.count
    epochs: list[list[float]] = []
    wait_s = 0.0
    profile = None
    if traced:
        profile = jax.profiler.ProfileOptions()
        profile.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=profile)
    w0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("bench.epoch"):
            res = eng.fit()
        epochs.append(res.losses)
        wait_s += res.metrics["spans"].get("prefetch.wait",
                                           {}).get("total_s", 0.0)
        if time.perf_counter() - w0 >= seconds:
            break
    window_s = time.perf_counter() - w0
    if traced:
        jax.profiler.stop_trace()
        obs.configure(enabled=False)
    window = {"seconds": window_s, "epochs": len(epochs),
              "snapshots": len(epochs) * c["num_steps"],
              "compiles": clock.count - compiles0}
    log(f"window {window!r}")
    losses = np.asarray(epochs, np.float64)
    failed = int(np.sum(~np.isfinite(losses)))

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell.chips])
    transfer = (sum(res.per_shard_bytes) if res.per_shard_bytes
                else res.transfer_report["graph_diff"])
    prog_grad, prog_change = program_readings(rec, cell)
    del eng, rr, rec, res
    gc.collect()

    # ---- the check, once the program's state is freed ----
    r0 = time.perf_counter()
    ref = reference_readings(cell, reference_slices(cell, raw), seed)
    numbers = check.compare(epochs, prog_grad, prog_change, ref)
    log(f"reference {time.perf_counter() - r0:.1f} s, losses {ref.losses}")
    limits = c["limits"]
    correct = failed == 0 and check.verdict(numbers, limits)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct),
           "attempted": int(losses.size * cell.slice_len),
           "failed": failed * cell.slice_len}
    if not traced:
        out["metrics"] = {
            "setup_s": {"value": setup["total_s"], "unit": "s"},
            "train_snapshots_per_s": {
                "value": window["snapshots"] / window_s,
                "unit": "snapshots/s"},
            "peak_hbm_gib": {"value": peak / GIB, "unit": "GiB"}}
    else:
        import trace_reduce
        red = trace_reduce.reduce_dir(
            trace_dir, chips=cell.chips, epochs=window["epochs"],
            rounds=window["epochs"] * cell.steps_per_epoch)
        shape = shape_of(cell)
        mod = reference.model(c["model"])
        ctx = Context(
            cell=cell, peaks=peaks, setup=setup, window=window,
            spans={"prefetch.wait": wait_s},
            transfer_bytes_per_epoch=float(transfer), trace=red,
            work={"flops": mod.flops_per_snapshot(shape)["total"],
                  "bytes": mod.bytes_per_snapshot(shape)["total"]})
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = metrics
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        out["breakdown"] = red.breakdown
    out["device"] = device
    out["check"] = {k: {"value": numbers[k], "limit": limits[k]}
                    for k in check.NUMBERS}
    return out
