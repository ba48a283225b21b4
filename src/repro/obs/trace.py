"""Thread-safe phase tracer: nested spans on one monotonic clock.

Two primitives, with deliberately different disabled-path contracts:

* ``Tracer.span(name)`` — a *pure* span.  When the tracer is disabled it
  returns a shared null object and performs **zero clock reads**; hot
  loops can leave spans inline at no cost (the <2% overhead bound is
  asserted by ``benchmarks/obs_bench.py``).
* ``Tracer.stopwatch(name)`` — an *always-on* measurement.  It reads the
  clock whether or not tracing is enabled (its ``.seconds`` feeds the
  legacy report fields: ``SampleReport.stage_seconds``,
  ``ServeResult.ingest_seconds``, ``RescaleEvent.recompose_s``, …) and
  additionally records a span when tracing is on.  This is the migration
  target for the ad-hoc ``time.perf_counter()`` pairs that used to live
  in ``src/`` (now a dynlint violation outside ``obs/`` and ``ft/``).

Spans are stored in a bounded ring (``collections.deque(maxlen=…)``);
once full, the oldest spans are evicted and counted in
``Tracer.dropped``.  All timestamps come from ``time.perf_counter_ns``
relative to the tracer's epoch, so spans from every thread share one
clock.  Device work is asynchronous under jax — with ``fence=True``
(the default for an enabled tracer) a span exit calls
``jax.block_until_ready`` on whatever the span registered via
``Span.fence(obj)``, so device phases measure *execution*, not
dispatch.  Fencing serializes the dispatch pipeline — a traced run
measures a serial schedule.

An enabled tracer also mirrors every span it times (``Span`` and
``Stopwatch``) as a ``jax.profiler.TraceAnnotation`` of the same name,
with the span's attributes and ``cat`` as metadata: under
``jax.profiler`` each span lands in the ``.xplane.pb`` on the
profiler's clock, on its own thread's line, beside the device ops.  A
disabled tracer opens none.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Iterator

__all__ = ["Span", "Stopwatch", "Tracer", "NULL_SPAN"]


def _annotation(name: str, cat: str, attrs: dict[str, Any]):
    """An entered ``jax.profiler.TraceAnnotation`` mirroring one span.
    Its metadata is the span's attributes plus its category, which also
    tells the program's spans from JAX's own events in the trace."""
    import jax
    ann = jax.profiler.TraceAnnotation(name, cat=cat, **attrs)
    ann.__enter__()
    return ann


class Span:
    """One timed region.  Use as a context manager; ``fence(obj)``
    registers jax arrays to block on at exit (only honoured when the
    owning tracer fences)."""

    __slots__ = ("name", "cat", "tid", "thread_name", "start_s", "dur_s",
                 "attrs", "_fence_obj", "_tracer", "_t0_ns", "_ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 attrs: dict[str, Any]):
        self.name = name
        self.cat = cat
        self.attrs = attrs
        self.tid = threading.get_ident()
        self.thread_name = threading.current_thread().name
        self.start_s = 0.0
        self.dur_s = 0.0
        self._fence_obj: Any = None
        self._tracer = tracer
        self._t0_ns = 0
        self._ann = None

    def fence(self, obj: Any) -> Any:
        """Register ``obj`` (pytree of jax arrays) to block on at span
        exit; returns ``obj`` so call sites can fence inline."""
        self._fence_obj = obj
        return obj

    def __enter__(self) -> "Span":
        self._ann = _annotation(self.name, self.cat, self.attrs)
        self._t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        tr = self._tracer
        try:
            if self._fence_obj is not None and tr.fencing:
                import jax
                jax.block_until_ready(self._fence_obj)
                self._fence_obj = None
            end_ns = time.perf_counter_ns()
        finally:
            self._ann.__exit__(*exc)
            self._ann = None
        self.start_s = (self._t0_ns - tr._epoch_ns) * 1e-9
        self.dur_s = (end_ns - self._t0_ns) * 1e-9
        tr._record(self)

    # convenience for symmetric reading with Stopwatch
    @property
    def seconds(self) -> float:
        return self.dur_s


class _NullSpan:
    """Shared no-op span: the disabled-tracer fast path.  No clock
    reads, no allocation per use."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def fence(self, obj: Any) -> Any:
        return obj

    name = ""
    cat = ""
    start_s = 0.0
    dur_s = 0.0
    seconds = 0.0
    attrs: dict[str, Any] = {}


NULL_SPAN = _NullSpan()


class Stopwatch:
    """Always-times context manager.  ``.seconds`` is valid after exit
    regardless of tracer state; a span is recorded only when tracing."""

    __slots__ = ("name", "cat", "attrs", "seconds", "start_s", "_tracer",
                 "_t0_ns", "_fence_obj", "_ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 attrs: dict[str, Any]):
        self.name = name
        self.cat = cat
        self.attrs = attrs
        self.seconds = 0.0
        self.start_s = 0.0
        self._tracer = tracer
        self._t0_ns = 0
        self._fence_obj: Any = None
        self._ann = None

    def fence(self, obj: Any) -> Any:
        """Like ``Span.fence`` — only honoured when the tracer fences,
        so an untraced run keeps its async dispatch schedule."""
        self._fence_obj = obj
        return obj

    def __enter__(self) -> "Stopwatch":
        if self._tracer.enabled:
            self._ann = _annotation(self.name, self.cat, self.attrs)
        self._t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        tr = self._tracer
        try:
            if self._fence_obj is not None and tr.enabled and tr.fencing:
                import jax
                jax.block_until_ready(self._fence_obj)
                self._fence_obj = None
            end_ns = time.perf_counter_ns()
        finally:
            if self._ann is not None:
                self._ann.__exit__(*exc)
                self._ann = None
        self.start_s = (self._t0_ns - tr._epoch_ns) * 1e-9
        self.seconds = (end_ns - self._t0_ns) * 1e-9
        if tr.enabled:
            sp = Span(tr, self.name, self.cat, self.attrs)
            sp.start_s = self.start_s
            sp.dur_s = self.seconds
            tr._record(sp)


class Tracer:
    """Bounded-ring span recorder shared by every instrumented layer.

    ``enabled=False`` (the default) is a true no-op for ``span()``:
    one attribute read and the shared ``NULL_SPAN`` — nothing else.
    """

    def __init__(self, enabled: bool = False, capacity: int = 65536,
                 fence: bool = True):
        self.enabled = bool(enabled)
        self.fencing = bool(fence)
        self.capacity = int(capacity)
        self.recorded = 0          # total spans ever recorded
        self._spans: deque[Span] = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._epoch_ns = time.perf_counter_ns()

    # ------------------------------------------------------------ record

    def span(self, name: str, cat: str = "phase", **attrs: Any):
        """Pure span: no-op (no clock read) when disabled."""
        if not self.enabled:
            return NULL_SPAN
        return Span(self, name, cat, attrs)

    def stopwatch(self, name: str, cat: str = "phase",
                  **attrs: Any) -> Stopwatch:
        """Always-measuring stopwatch (span recorded only if enabled)."""
        return Stopwatch(self, name, cat, attrs)

    def add_span(self, name: str, start_s: float, dur_s: float,
                 cat: str = "derived", tid: int | None = None,
                 **attrs: Any) -> None:
        """Inject a span with explicit timing (replayed measurements).
        Not mirrored to the profiler: it was never open.  No-op when
        disabled."""
        if not self.enabled:
            return
        sp = Span(self, name, cat, attrs)
        sp.start_s = float(start_s)
        sp.dur_s = float(dur_s)
        if tid is not None:
            sp.tid = tid
        self._record(sp)

    def _record(self, span: Span) -> None:
        with self._lock:
            self._spans.append(span)
            self.recorded += 1

    # ------------------------------------------------------------- query

    def now_s(self) -> float:
        """Seconds since the tracer epoch — the span clock.  Use this
        (not raw perf_counter) for latency bookkeeping outside spans."""
        return (time.perf_counter_ns() - self._epoch_ns) * 1e-9

    def spans(self) -> list[Span]:
        """Snapshot of the ring (oldest first)."""
        with self._lock:
            return list(self._spans)

    def spans_since(self, recorded_before: int) -> list[Span]:
        """Spans recorded after a ``tracer.recorded`` checkpoint (up to
        ring capacity — older ones may have been evicted)."""
        with self._lock:
            n = min(self.recorded - recorded_before, len(self._spans))
            if n <= 0:
                return []
            return list(self._spans)[-n:]

    @property
    def dropped(self) -> int:
        """Spans evicted from the ring (recorded but no longer stored)."""
        with self._lock:
            return self.recorded - len(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self.recorded = 0

    def summary(self, spans: list[Span] | None = None) -> dict[str, dict]:
        """Per-name aggregate: count / total_s / mean_s / max_s."""
        out: dict[str, dict] = {}
        for sp in (self.spans() if spans is None else spans):
            agg = out.setdefault(sp.name, {"count": 0, "total_s": 0.0,
                                           "max_s": 0.0})
            agg["count"] += 1
            agg["total_s"] += sp.dur_s
            agg["max_s"] = max(agg["max_s"], sp.dur_s)
        for agg in out.values():
            agg["mean_s"] = agg["total_s"] / agg["count"]
        return out

    def __iter__(self) -> Iterator[Span]:
        return iter(self.spans())
