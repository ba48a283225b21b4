"""Split the traced window by the program's stages and host spans.

The program names its step's stages with ``jax.named_scope`` (``STAGES``
below; the program's own list is ``repro.obs.stages``), so every device
op carries its stage in XLA's ``op_name``.  On a TPU the profiler keeps
that name as the ``tf_op`` stat of the op's event metadata, which
``jax.profiler.ProfileData`` does not expose, and leaves it out for some
ops (the scatter fusions, whose root lost it in compilation).  So this
module reads the ``.xplane.pb`` itself, with a schema of the few XPlane
and HLO fields it needs: an op without ``tf_op`` takes the ``op_name`` of
its instruction in the program's HLO, which the trace also carries (the
``Hlo Proto`` of each program on the ``/host:metadata`` plane), else that
of the instructions it calls: its root, else the last one that has one.

What it reads, in the window that ``trace_reduce`` uses (the span of the
``bench.epoch`` annotations):

* device seconds per (stage, pass), summed over the cell's chips: each
  ``XLA Ops`` event goes to the innermost stage of its name stack, and
  to ``bwd`` when the name stack holds a ``transpose(...)``; ops with no
  stage go to ``unscoped``, so the stages and ``unscoped`` partition the
  op time exactly;
* the program's host spans: the profiler annotations that the program's
  tracer opens for its spans carry a ``cat`` stat, which tells them
  from JAX's own events; their seconds in the window, per name;
* chip 0's idle time, the share of it during which a program span is
  open on the host, and its longest gaps, each labelled with the
  innermost program span of every thread over it (JAX's own event only
  where no program span is open).

A trace of a program without stage scopes reads ``scoped`` False, and
one without mirrored spans no span at all: the readers then return None.

    python3 bench/stages.py <trace_dir> --chips <n> --snapshots <n>

prints the reduction of a kept trace (``bench/run.py --trace-dir``).
"""

from __future__ import annotations

import argparse
import glob
import json
import re
import sys
from dataclasses import dataclass, field
from pathlib import Path

from trace_reduce import DEVICE_PLANE, HOST_PLANE, OP_LINE, WINDOW_MARK, gaps

METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
PROGRAM_STAT = "program_id"
OP_NAME_STAT = "tf_op"
SPAN_STAT = "cat"
UNSCOPED = "unscoped"

# the program's stage scopes (``repro.obs.stages``)
STAGES = ("delta_apply", "edge_weights", "spatial", "spmm", "temporal",
          "a2a", "loss", "optimizer")
# a name-stack component wrapped by a transformation: jvp(spatial),
# transpose(jvp(spatial)), vmap(spmm), jit(_take), ...
_WRAPPED = re.compile(r"^[\w.]*\((.*)\)$")

_SCHEMA = """
name: "bench_xplane.proto" package: "bench_xplane" syntax: "proto2"
message_type { name: "XSpace"
  field { name: "planes" number: 1 label: LABEL_REPEATED type: TYPE_MESSAGE
          type_name: ".bench_xplane.XPlane" } }
message_type { name: "XPlane"
  field { name: "name" number: 2 label: LABEL_OPTIONAL type: TYPE_STRING }
  field { name: "lines" number: 3 label: LABEL_REPEATED type: TYPE_MESSAGE
          type_name: ".bench_xplane.XLine" }
  field { name: "event_metadata" number: 4 label: LABEL_REPEATED
          type: TYPE_MESSAGE type_name: ".bench_xplane.EventMetadataEntry" }
  field { name: "stat_metadata" number: 5 label: LABEL_REPEATED
          type: TYPE_MESSAGE type_name: ".bench_xplane.StatMetadataEntry" } }
message_type { name: "EventMetadataEntry"
  field { name: "key" number: 1 label: LABEL_OPTIONAL type: TYPE_INT64 }
  field { name: "value" number: 2 label: LABEL_OPTIONAL type: TYPE_MESSAGE
          type_name: ".bench_xplane.XEventMetadata" } }
message_type { name: "StatMetadataEntry"
  field { name: "key" number: 1 label: LABEL_OPTIONAL type: TYPE_INT64 }
  field { name: "value" number: 2 label: LABEL_OPTIONAL type: TYPE_MESSAGE
          type_name: ".bench_xplane.XStatMetadata" } }
message_type { name: "XLine"
  field { name: "id" number: 1 label: LABEL_OPTIONAL type: TYPE_INT64 }
  field { name: "name" number: 2 label: LABEL_OPTIONAL type: TYPE_STRING }
  field { name: "timestamp_ns" number: 3 label: LABEL_OPTIONAL
          type: TYPE_INT64 }
  field { name: "events" number: 4 label: LABEL_REPEATED type: TYPE_MESSAGE
          type_name: ".bench_xplane.XEvent" } }
message_type { name: "XEvent"
  field { name: "metadata_id" number: 1 label: LABEL_OPTIONAL
          type: TYPE_INT64 }
  field { name: "offset_ps" number: 2 label: LABEL_OPTIONAL type: TYPE_INT64 }
  field { name: "duration_ps" number: 3 label: LABEL_OPTIONAL
          type: TYPE_INT64 }
  field { name: "stats" number: 4 label: LABEL_REPEATED type: TYPE_MESSAGE
          type_name: ".bench_xplane.XStat" } }
message_type { name: "XStat"
  field { name: "metadata_id" number: 1 label: LABEL_OPTIONAL
          type: TYPE_INT64 }
  field { name: "uint64_value" number: 3 label: LABEL_OPTIONAL
          type: TYPE_UINT64 }
  field { name: "int64_value" number: 4 label: LABEL_OPTIONAL
          type: TYPE_INT64 }
  field { name: "str_value" number: 5 label: LABEL_OPTIONAL
          type: TYPE_STRING }
  field { name: "bytes_value" number: 6 label: LABEL_OPTIONAL
          type: TYPE_BYTES }
  field { name: "ref_value" number: 7 label: LABEL_OPTIONAL
          type: TYPE_UINT64 } }
message_type { name: "XEventMetadata"
  field { name: "id" number: 1 label: LABEL_OPTIONAL type: TYPE_INT64 }
  field { name: "name" number: 2 label: LABEL_OPTIONAL type: TYPE_STRING }
  field { name: "display_name" number: 4 label: LABEL_OPTIONAL
          type: TYPE_STRING }
  field { name: "stats" number: 5 label: LABEL_REPEATED type: TYPE_MESSAGE
          type_name: ".bench_xplane.XStat" } }
message_type { name: "XStatMetadata"
  field { name: "id" number: 1 label: LABEL_OPTIONAL type: TYPE_INT64 }
  field { name: "name" number: 2 label: LABEL_OPTIONAL type: TYPE_STRING } }
message_type { name: "HloProto"
  field { name: "hlo_module" number: 1 label: LABEL_OPTIONAL
          type: TYPE_MESSAGE type_name: ".bench_xplane.HloModuleProto" } }
message_type { name: "HloModuleProto"
  field { name: "name" number: 1 label: LABEL_OPTIONAL type: TYPE_STRING }
  field { name: "computations" number: 3 label: LABEL_REPEATED
          type: TYPE_MESSAGE type_name: ".bench_xplane.HloComputationProto" } }
message_type { name: "HloComputationProto"
  field { name: "instructions" number: 2 label: LABEL_REPEATED
          type: TYPE_MESSAGE type_name: ".bench_xplane.HloInstructionProto" }
  field { name: "id" number: 5 label: LABEL_OPTIONAL type: TYPE_INT64 }
  field { name: "root_id" number: 6 label: LABEL_OPTIONAL type: TYPE_INT64 } }
message_type { name: "HloInstructionProto"
  field { name: "name" number: 1 label: LABEL_OPTIONAL type: TYPE_STRING }
  field { name: "metadata" number: 7 label: LABEL_OPTIONAL
          type: TYPE_MESSAGE type_name: ".bench_xplane.OpMetadata" }
  field { name: "id" number: 35 label: LABEL_OPTIONAL type: TYPE_INT64 }
  field { name: "called_computation_ids" number: 38
          label: LABEL_REPEATED type: TYPE_INT64 } }
message_type { name: "OpMetadata"
  field { name: "op_name" number: 2 label: LABEL_OPTIONAL
          type: TYPE_STRING } }
"""
_CLASSES: dict = {}


def message_class(name: str):
    """The class of ``bench_xplane.<name>`` (``XSpace``, ``HloProto``)."""
    if not _CLASSES:
        from google.protobuf import (descriptor_pb2, descriptor_pool,
                                     message_factory, text_format)
        pool = descriptor_pool.DescriptorPool()
        pool.Add(text_format.Parse(_SCHEMA,
                                   descriptor_pb2.FileDescriptorProto()))
        for msg in ("XSpace", "HloProto"):
            _CLASSES[msg] = message_factory.GetMessageClass(
                pool.FindMessageTypeByName(f"bench_xplane.{msg}"))
    return _CLASSES[name]


@dataclass
class Event:
    start_ps: int               # exact: float ns lose the ns at 1e18
    end_ps: int
    name: str
    stats: dict
    display_name: str = ""


def _lines(plane):
    """(line, its events) of a plane; each event's stats are its
    metadata's, overridden by its own."""
    meta = {e.key: e.value for e in plane.event_metadata}
    stat_names = {s.key: s.value.name for s in plane.stat_metadata}

    def stats(xs):
        out = {}
        for s in xs:
            if s.HasField("ref_value"):
                v = stat_names.get(s.ref_value, "")
            elif s.HasField("str_value"):
                v = s.str_value
            elif s.HasField("bytes_value"):
                v = s.bytes_value
            elif s.HasField("uint64_value"):
                v = s.uint64_value
            else:
                v = s.int64_value
            out[stat_names.get(s.metadata_id, str(s.metadata_id))] = v
        return out

    md_stats = {k: stats(md.stats) for k, md in meta.items()}
    for line in plane.lines:
        events = []
        for e in line.events:
            md = meta.get(e.metadata_id)
            merged = dict(md_stats.get(e.metadata_id, {}))
            merged.update(stats(e.stats))
            start = line.timestamp_ns * 1000 + e.offset_ps
            events.append(Event(start, start + e.duration_ps,
                                md.name if md is not None else "", merged,
                                md.display_name if md is not None else ""))
        yield line, events


class OpNames:
    """XLA ``op_name`` of each instruction of each program in the trace,
    from the HLO protos on its metadata plane.  An instruction whose own
    ``op_name`` is no name stack (empty, or a bare ``add`` of a reducer)
    takes its called computations': the root's, else the last
    instruction's in program order that has one, else the same of the
    computations those call."""

    def __init__(self, space):
        self._modules: dict[int, tuple] = {}
        for plane in space.planes:
            if plane.name != METADATA_PLANE:
                continue
            for program, blob in _hlo_protos(plane):
                hlo = message_class("HloProto")()
                hlo.ParseFromString(blob)
                comps = {c.id: c for c in hlo.hlo_module.computations}
                instrs = {i.name: i for c in comps.values()
                          for i in c.instructions}
                self._modules[program] = (comps, instrs, {})

    def __call__(self, program: int, instruction: str) -> str:
        mod = self._modules.get(program)
        if mod is None or instruction not in mod[1]:
            return ""
        return self._of(mod, mod[1][instruction])

    @staticmethod
    def _own(ins) -> str:
        name = ins.metadata.op_name
        return name if "/" in name else ""

    def _of(self, mod, ins) -> str:
        return self._own(ins) or next(
            (n for n in (self._called(mod, c)
                         for c in ins.called_computation_ids) if n), "")

    def _called(self, mod, cid: int) -> str:
        comps, _, memo = mod
        if cid not in memo:
            memo[cid] = ""              # no cycles through a computation
            c = comps.get(cid)
            if c is not None:
                root = [i for i in c.instructions if i.id == c.root_id]
                order = root + list(reversed(c.instructions))
                memo[cid] = next((n for n in map(self._own, order) if n),
                                 "") or next(
                    (n for n in (self._of(mod, i) for i in order) if n), "")
        return memo[cid]


def _hlo_protos(plane):
    """(program id, serialized HLO proto) of each program that the
    metadata plane describes, by the id in its name (``jit_step(<id>)``)."""
    stat_names = {s.key: s.value.name for s in plane.stat_metadata}
    for md in (e.value for e in plane.event_metadata):
        m = re.search(r"\((\d+)\)$", md.name)
        for st in md.stats:
            if m and stat_names.get(st.metadata_id) == HLO_PROTO_STAT:
                yield int(m.group(1)), st.bytes_value


def stage_of(op_name: str) -> tuple[str, str]:
    """(innermost stage or ``unscoped``, ``fwd`` or ``bwd``) of an XLA
    ``op_name`` such as ``jit(step)/transpose(jvp(spatial))/vmap(spmm)/
    gather:``."""
    name = op_name.rsplit(":", 1)[0] if ":" in op_name else op_name
    stage, backward = UNSCOPED, False
    for part in name.split("/"):
        while (m := _WRAPPED.match(part)):
            backward |= part.startswith("transpose(")
            part = m.group(1)
        if part in STAGES:
            stage = part
    return stage, "bwd" if backward else "fwd"


@dataclass
class Stages:
    window_s: float
    op_s: float                 # every op in the window, all chips
    stage_s: dict = field(default_factory=dict)  # (stage, pass) -> s
    spans: dict = field(default_factory=dict)    # program span -> s
    idle_s: float = 0.0         # chip 0's idle time in the window
    idle_attributed_s: float = 0.0   # of it, under a program span
    gaps: list = field(default_factory=list)     # [label, s], chip 0
    device_ops: list = field(default_factory=list)  # [label, s]

    @property
    def scoped(self) -> bool:
        """Whether any op in the window carried a stage scope."""
        return any(st != UNSCOPED for st, _ in self.stage_s)

    def seconds(self, *stages: str) -> float:
        """Seconds under any of ``stages``, both passes."""
        return sum(s for (st, _), s in self.stage_s.items() if st in stages)


PS = 1e-12


def _clip(ev: Event, lo: int, hi: int) -> tuple[int, int]:
    return max(ev.start_ps, lo), min(ev.end_ps, hi)


def _overlap(a: int, b: int, intervals) -> int:
    """Length of [a, b] that the union of ``intervals`` covers."""
    return (b - a) - sum(d - c for c, d in gaps(intervals, a, b))


def reduce(space, *, chips: int) -> Stages:
    """Reduce a parsed XSpace (``read``)."""
    devices, host = {}, None
    for plane in space.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = plane
        elif plane.name == HOST_PLANE:
            host = plane
    if host is None or len(devices) < chips:
        raise RuntimeError(f"trace holds {len(devices)} TPU planes and "
                           f"{'a' if host else 'no'} host plane; the cell "
                           f"runs on {chips} chips")
    threads = [(line.name, evs) for line, evs in _lines(host)]
    marks = [e for _, evs in threads for e in evs if e.name == WINDOW_MARK]
    if not marks:
        raise RuntimeError(f"no {WINDOW_MARK!r} annotation in the trace")
    lo = min(e.start_ps for e in marks)
    hi = max(e.end_ps for e in marks)

    op_names = OpNames(space)
    stage_s: dict = {}
    op_s: dict = {}
    chip0 = []
    for i in sorted(devices)[:chips]:
        events = next((evs for ln, evs in _lines(devices[i])
                       if ln.name == OP_LINE), None)
        if events is None:
            raise RuntimeError(f"TPU plane {i} lacks the {OP_LINE!r} line")
        ops = []
        for e in events:
            a, b = _clip(e, lo, hi)
            if b <= a:
                continue
            name = e.stats.get(OP_NAME_STAT) or op_names(
                e.stats.get(PROGRAM_STAT, -1), e.display_name)
            key = stage_of(str(name))
            stage_s[key] = stage_s.get(key, 0.0) + (b - a) * PS
            label = f"{key[0]}/{key[1]} · {(e.display_name or e.name)[:64]}"
            op_s[label] = op_s.get(label, 0.0) + (b - a) * PS
            ops.append((a, b))
        if i == min(devices):
            chip0 = ops

    # program spans, per thread, clipped to the window
    program = [[(*_clip(e, lo, hi), e) for e in evs
                if SPAN_STAT in e.stats and e.end_ps > lo
                and e.start_ps < hi] for _, evs in threads]
    spans: dict = {}
    for per_thread in program:
        for a, b, e in per_thread:
            spans[e.name] = spans.get(e.name, 0.0) + (b - a) * PS
    covered = [(a, b) for t in program for a, b, _ in t]
    idle = gaps(chip0, lo, hi)
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:10]
    return Stages(
        window_s=(hi - lo) * PS, op_s=sum(stage_s.values()),
        stage_s=stage_s, spans=spans,
        idle_s=sum(b - a for a, b in idle) * PS,
        idle_attributed_s=sum(_overlap(a, b, covered)
                              for a, b in idle) * PS,
        gaps=[[gap_label(program, threads, a, b), (b - a) * PS]
              for a, b in longest],
        device_ops=[[n, s] for n, s in sorted(
            op_s.items(), key=lambda kv: -kv[1])[:10]])


def gap_label(program, threads, a: int, b: int) -> str:
    """The program spans open over [a, b]: on each thread the one that
    overlaps the gap most (the innermost, shorter one on a tie), joined
    across threads.  Where no program span is open, JAX's own host event
    that overlaps the gap most, as ``<thread>: <event>``."""
    def best(candidates):
        top, key = None, None
        for s, e, name in candidates:
            if e <= a or s >= b:
                continue
            k = (min(e, b) - max(s, a), -(e - s))
            if key is None or k > key:
                top, key = name, k
        return top

    names = [best((s, e, ev.name) for s, e, ev in per_thread)
             for per_thread in program]
    names = [n for n in names if n is not None]
    if names:
        return " + ".join(dict.fromkeys(names))
    top = best((e.start_ps, e.end_ps, f"{thread}: {e.name}")
               for thread, evs in threads for e in evs
               if e.name != WINDOW_MARK)
    return top or "host idle"


def read(path: str):
    """The parsed XSpace of one ``.xplane.pb`` file."""
    space = message_class("XSpace")()
    space.ParseFromString(Path(path).read_bytes())
    return space


def from_text(text: str):
    """The parsed XSpace of a text-format XSpace (test fixtures)."""
    from jax.profiler import ProfileData
    space = message_class("XSpace")()
    space.ParseFromString(ProfileData.text_proto_to_serialized_xspace(text))
    return space


def reduce_dir(trace_dir: str, *, chips: int) -> Stages:
    """Reduce the one ``.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return reduce(read(paths[0]), chips=chips)


# ---------------------------------------------------------------- readers ---

_last: tuple | None = None


def of(ctx) -> Stages | None:
    """The stage reduction of ``ctx``'s traced window, once per Context;
    None for an untraced run or where the trace cannot be found.

    A Context with a ``stages`` attribute is taken at its word.  Else
    the trace is the one ``cell.run`` wrote: it keeps the profiler
    trace in its local ``trace_dir`` until the per-layer readers have
    returned but passes them no path to it, so the path is read from
    that frame of the call stack."""
    global _last
    if hasattr(ctx, "stages"):
        return ctx.stages
    if ctx.trace is None:
        return None
    if _last is not None and _last[0] is ctx:
        return _last[1]
    path = _harness_trace_dir()
    red = reduce_dir(path, chips=ctx.cell.chips) if path else None
    _last = (ctx, red)
    return red


def _harness_trace_dir() -> str | None:
    frame = sys._getframe(1)
    while frame is not None:
        code = frame.f_code
        if code.co_name == "run" and Path(code.co_filename).name == "cell.py":
            d = frame.f_locals.get("trace_dir")
            return d if isinstance(d, str) else None
        frame = frame.f_back
    return None


def per_snapshot_ms(ctx, *stages: str) -> float | None:
    """Device milliseconds per snapshot under ``stages`` (both passes,
    all chips); None where the trace holds no stage scope."""
    red = of(ctx)
    if red is None or not red.scoped:
        return None
    return 1e3 * red.seconds(*stages) / ctx.window["snapshots"]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--snapshots", type=int, required=True,
                    help="snapshot steps the traced window ran")
    args = ap.parse_args(argv)
    red = reduce_dir(args.trace_dir, chips=args.chips)
    per = 1e3 / args.snapshots
    print(json.dumps({
        "window_s": red.window_s, "op_ms_per_snapshot": red.op_s * per,
        "stage_ms_per_snapshot": {f"{st}/{p}": s * per for (st, p), s
                                  in sorted(red.stage_s.items())},
        "span_ms_per_snapshot": {n: s * per
                                 for n, s in sorted(red.spans.items())},
        "idle_s": red.idle_s, "idle_attributed_s": red.idle_attributed_s,
        "idle_gaps": red.gaps, "device_ops": red.device_ops}, indent=1))


if __name__ == "__main__":
    main()
