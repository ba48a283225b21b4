"""Reduce a profiler trace of the measured window to per-layer numbers.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``.  What it holds on a TPU:

* one plane per chip, ``/device:TPU:<i>``, with a line of whole programs
  (``XLA Modules``: one event per run of a jitted program, named
  ``<module>(<id>)``) and a line of the operations inside them
  (``XLA Ops``);
* a host plane, ``/host:CPU``, with one line per thread; the benchmark's
  ``jax.profiler.TraceAnnotation`` ``bench.epoch`` spans each timed epoch,
  and JAX's own events (``PjitFunction(<name>)``, transfers) say what
  the host was doing.

The window is the span of the ``bench.epoch`` annotations, one per
epoch.  Busy time is the union of a chip's operation intervals in it.
The step's device time is the sum, over chips, of every module the
window runs: the snapshot step's path (the train step and the jits that
rebuild its edges) and each epoch's re-initialisation of the state the
steps start from.  So no work can leave the step time by moving from one
module to another.  Every module must be named in ``STEP_MODULES`` or
``EPOCH_MODULES``, and an epoch module may run only before its epoch's
first train step: anything else fails the reduction, so that no share
is ever taken of a step whose structure the lists do not describe.

Modules go to epochs by their order on each chip, not by the host's
marks: the device's clock reads ahead of the host's by up to a
millisecond, more as the window goes on, so an epoch's parameter draw
can start on the trace before the mark the host opened before drawing
it.  On each chip the first epoch opens at the window's start, and an
epoch module that follows a train step opens the next.  Every chip must
run the window's ``rounds`` train steps, and a chip that runs epoch
modules must split them into the window's ``epochs``, each holding the
same number: an epoch module between two steps of one epoch makes an
epoch too many.  The window must hold one ``bench.epoch`` mark for each
of its ``epochs``.  What the order cannot tell: an epoch module that runs
between the last step of one epoch and the first of the next, where the
next epoch's draw runs, is taken for part of that draw.  Its time still
counts in the step time; only its name goes unchecked.
"""

from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
WINDOW_MARK = "bench.epoch"
ALL_TO_ALL = re.compile(r"all-to-all")

# The programs a timed epoch runs, by the name JAX gives each jit.  The
# snapshot step's path: the train step (one snapshot, or one round on the
# mesh), the delta-apply and copy jits that rebuild its edge lists, the
# stacking of a round's slots (concatenate, and broadcast_in_dim for the
# stacked axis, which also makes the epoch's zero carries, optimizer
# state and rings), and the per-step offset's conversion.
TRAIN_STEP = "jit_step"
STEP_MODULES = frozenset({
    TRAIN_STEP, "jit_apply_delta", "jit_copy", "jit_concatenate",
    "jit_broadcast_in_dim", "jit_convert_element_type"})
# before each epoch's first train step: the parameters drawn from the seed
# (EvolveGCN's also fold a key in)
EPOCH_MODULES = frozenset({
    "jit__threefry_seed", "jit__threefry_split", "jit__threefry_fold_in",
    "jit__uniform", "jit__unstack", "jit__multi_slice", "jit_negative",
    "jit_sqrt", "jit_true_divide"})

class UnknownModule(RuntimeError):
    """The window ran a device program that neither list names, an
    epoch's set-up program after that epoch's first train step, or not
    the window's train steps."""


def module_name(event_name: str) -> str:
    """``jit_step(123)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def union_length(intervals: list[tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def gaps(intervals: list[tuple[float, float]], lo: float,
         hi: float) -> list[tuple[float, float]]:
    """Stretches of [lo, hi] that no interval covers."""
    out, end = [], lo
    for a, b in sorted(intervals):
        if a > end:
            out.append((end, min(a, hi)))
        end = max(end, b)
        if end >= hi:
            break
    if end < hi:
        out.append((end, hi))
    return [(a, b) for a, b in out if b > a]


def _subtract(a: float, b: float, cover: list[tuple[float, float]]) -> float:
    """Length of [a, b] not covered by the sorted, merged ``cover``."""
    left = b - a
    for c, d in cover:
        if d <= a:
            continue
        if c >= b:
            break
        left -= min(b, d) - max(a, c)
    return left


def _merge(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


@dataclass
class Reduction:
    window_s: float
    busy_s: float               # mean over the chips
    step_device_s: float        # every module, summed over the chips
    a2a_exposed_s: float        # summed over the chips
    rounds: int
    modules: dict = field(default_factory=dict)     # seconds, all chips
    breakdown: dict = field(default_factory=dict)


def reduce(profile, *, chips: int, epochs: int, rounds: int) -> Reduction:
    """Reduce a ``jax.profiler.ProfileData`` of a window that ran
    ``epochs`` epochs of ``rounds`` train steps in all."""
    devices, host = {}, None
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = plane
        elif plane.name == HOST_PLANE:
            host = plane
    if host is None or len(devices) < chips:
        raise RuntimeError(f"trace holds {len(devices)} TPU planes and "
                           f"{'a' if host else 'no'} host plane; the cell "
                           f"runs on {chips} chips")
    host_events = []
    for line in host.lines:
        for e in line.events:
            host_events.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name, line.name))
    marks = [(a, b) for a, b, n, _ in host_events if n == WINDOW_MARK]
    if not marks:
        raise RuntimeError(f"no {WINDOW_MARK!r} annotation in the trace")
    if len(marks) != epochs:
        raise RuntimeError(f"the trace holds {len(marks)} {WINDOW_MARK!r} "
                           f"marks; the window ran {epochs} epochs")
    lo, hi = min(a for a, _ in marks), max(b for _, b in marks)

    def inside(e):
        return e.start_ns < hi and e.start_ns + e.duration_ns > lo

    modules: dict[str, float] = {}
    op_time: dict[str, float] = {}
    busy, step, exposed = [], 0.0, 0.0
    chip0_busy = []
    for i in sorted(devices)[:chips]:
        lines = {ln.name: ln for ln in devices[i].lines}
        if MODULE_LINE not in lines or OP_LINE not in lines:
            raise RuntimeError(f"TPU plane {i} lacks the {MODULE_LINE!r} "
                               f"or {OP_LINE!r} line")
        steps = [0]                 # train steps of each epoch, in order
        redrawn = False             # whether an epoch module ran
        for e in sorted((e for e in lines[MODULE_LINE].events if inside(e)),
                        key=lambda e: e.start_ns):
            name = module_name(e.name)
            if name == TRAIN_STEP:
                steps[-1] += 1
            elif name in EPOCH_MODULES:
                redrawn = True
                if steps[-1]:
                    steps.append(0)
            elif name not in STEP_MODULES:
                raise UnknownModule(
                    f"the window ran device module {name!r}, which "
                    "trace_reduce lists neither as a step module nor as an "
                    "epoch module")
            secs = (min(e.start_ns + e.duration_ns, hi)
                    - max(e.start_ns, lo)) * 1e-9
            modules[name] = modules.get(name, 0.0) + secs
            step += secs
        _check_epochs(i, steps, redrawn, epochs, rounds)
        ops = [(max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi),
                e.name) for e in lines[OP_LINE].events if inside(e)]
        for a, b, n in ops:
            op_time[n] = op_time.get(n, 0.0) + (b - a) * 1e-9
        spans = [(a, b) for a, b, _ in ops]
        busy.append(union_length(spans) * 1e-9)
        if i == min(devices):
            chip0_busy = spans
        compute = _merge([(a, b) for a, b, n in ops
                          if not ALL_TO_ALL.search(n)])
        for a, b, n in ops:
            if ALL_TO_ALL.search(n):
                exposed += _subtract(a, b, compute) * 1e-9
    window_s = (hi - lo) * 1e-9
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(gaps(chip0_busy, lo, hi), key=lambda g: g[0] - g[1])[:10]
    return Reduction(
        window_s=window_s, busy_s=sum(busy) / len(busy), step_device_s=step,
        a2a_exposed_s=exposed, rounds=rounds, modules=modules,
        breakdown={"device_ops": [[n, s] for n, s in top_ops],
                   "idle_gaps": [[_host_activity(host_events, a, b),
                                  (b - a) * 1e-9] for a, b in idle]})


def _check_epochs(chip: int, steps: list[int], redrawn: bool, epochs: int,
                  rounds: int) -> None:
    """Chip ``chip`` ran ``steps[k]`` train steps in its k-th epoch."""
    if sum(steps) != rounds:
        raise UnknownModule(
            f"TPU {chip} ran {sum(steps)} train steps in the window, which "
            f"ran {rounds}: a step under another name, or one left out")
    if redrawn and steps != [rounds // epochs] * epochs:
        raise UnknownModule(
            f"TPU {chip} ran its epoch modules between train steps "
            f"{steps}, where the window ran {epochs} epochs of "
            f"{rounds // epochs}: an epoch module after its epoch's first "
            "train step, step work in a module trace_reduce takes for the "
            "epoch's re-initialisation")


def _host_activity(host_events, a: float, b: float) -> str:
    """What the host was doing in the gap [a, b]: the host event, other
    than the epoch annotation, that overlaps the gap most (the shorter
    one on a tie), as ``<thread>: <event>``."""
    best, key = "host idle", None
    for s, e, name, thread in host_events:
        if name == WINDOW_MARK or e <= a or s >= b:
            continue
        k = (min(e, b) - max(s, a), -(e - s))
        if key is None or k > key:
            best, key = f"{thread}: {name}", k
    return best


def reduce_dir(trace_dir: str, *, chips: int, epochs: int,
               rounds: int) -> Reduction:
    """Reduce the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return reduce(ProfileData.from_file(paths[0]), chips=chips,
                  epochs=epochs, rounds=rounds)
