"""The program's own spans and scopes in a traced run.

The program marks its layers itself (``repro/tracing.py``): host spans
(``serve.*``, ``train.*``, ``host.gc``) written by
``jax.profiler.TraceAnnotation`` on the profiler's clock, and named scopes
inside its jitted programs, which reach each device op as its ``op_name``
metadata. On the TPU trace that metadata is the ``tf_op`` stat of the op's
event metadata (``jit(_paged_step_impl)/gather/add:``), which
``jax.profiler.ProfileData`` does not expose; so this module reads the
``.xplane.pb`` itself, through a minimal copy of the XPlane schema.

One parse per trace (``of_run``) keeps the first chip's ops, with their
scope paths, its program modules, and the program's and the benchmark's
host spans, windowed by the benchmark's ``bench.window`` span. The metric
readers under ``bench/metrics/`` ask it for scoped device time, program
time, program calls and host stretches. A trace without the program's
spans (a program that predates them) reads as nothing. The first parse
also logs the first chip's longest idle gaps, each put down to the
innermost program span covering it.
"""
from __future__ import annotations

import bisect
import functools
import gzip
import json
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from bench import harness as H
from bench import trace as TR

TRACE_DIR = "bench_out/trace"          # where bench/serve.py and train.py
WINDOW_SPAN = "bench.window"           # write the trace, and its window
SCOPE_STAT = "tf_op"                   # the op_name metadata of a TPU op
PROGRAM_SPANS = ("serve.", "train.", "host.")
HOST_SPANS = PROGRAM_SPANS + (TR.HOST_SPAN_PREFIX,)
# page growth's programs (Engine.ensure_capacity): the pool blank and the
# block-table row setter
GROWTH = ("_blank_row_impl", "_set_table_row_impl")


@dataclass(frozen=True)
class Event(TR.Event):
    scope: str = ""                    # device op: its op_name path


# --- the XPlane schema, as much of it as is read ---------------------------
# (tsl/profiler/protobuf/xplane.proto; field numbers as there)
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane")],
    "XPlane": [("name", 2, str), ("lines", 3, "XLine"),
               ("event_metadata", 4, "EventMetadataEntry"),
               ("stat_metadata", 5, "StatMetadataEntry")],
    "EventMetadataEntry": [("key", 1, int), ("value", 2, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, int), ("value", 2, "XStatMetadata")],
    "XLine": [("name", 2, str), ("timestamp_ns", 3, int),
              ("events", 4, "XEvent")],
    "XEvent": [("metadata_id", 1, int), ("offset_ps", 2, int),
               ("duration_ps", 3, int)],
    "XEventMetadata": [("id", 1, int), ("name", 2, str),
                       ("stats", 5, "XStat")],
    "XStatMetadata": [("id", 1, int), ("name", 2, str)],
    "XStat": [("metadata_id", 1, int), ("str_value", 5, str),
              ("ref_value", 7, "uint64")],
}
_REPEATED = {"planes", "lines", "event_metadata", "stat_metadata", "events",
             "stats"}


@functools.lru_cache(maxsize=None)
def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    scalar = {str: F.TYPE_STRING, int: F.TYPE_INT64, "uint64": F.TYPE_UINT64}
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    for msg, fields in _SCHEMA.items():
        m = fd.message_type.add(name=msg)
        for name, num, kind in fields:
            f = m.field.add(name=name, number=num,
                            label=F.LABEL_REPEATED if name in _REPEATED
                            else F.LABEL_OPTIONAL)
            if kind in scalar:
                f.type = scalar[kind]
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, f".bench_xplane.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def events_from_xplane(path: str) -> List[Event]:
    """The first chip's ``XLA Ops`` (with each op's scope path) and ``XLA
    Modules`` events, and every host span of the program or the benchmark,
    from one ``.xplane.pb`` file; times in ns as ``ProfileData`` gives
    them (line timestamp plus the event's offset)."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    devices = sorted((int(m.group(1)), p) for p in space.planes
                     for m in [TR.DEVICE_PLANE.match(p.name)] if m)
    out: List[Event] = []
    if devices:
        plane = devices[0][1]
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        scope_id = next((k for k, v in stat_names.items()
                         if v == SCOPE_STAT), None)
        names, scopes = {}, {}
        for e in plane.event_metadata:
            names[e.key] = e.value.name
            for s in e.value.stats:
                if s.metadata_id == scope_id:
                    scopes[e.key] = s.str_value or stat_names.get(
                        s.ref_value, "")
        for line in plane.lines:
            if line.name not in (TR.OPS_LINE, TR.MODULES_LINE):
                continue
            t0 = float(line.timestamp_ns)
            for ev in line.events:
                out.append(Event(plane.name, line.name,
                                 names.get(ev.metadata_id, ""),
                                 t0 + ev.offset_ps / 1e3,
                                 ev.duration_ps / 1e3,
                                 scopes.get(ev.metadata_id, "")))
    for plane in space.planes:
        if TR.DEVICE_PLANE.match(plane.name):
            continue
        names = {e.key: e.value.name for e in plane.event_metadata}
        for line in plane.lines:
            t0 = float(line.timestamp_ns)
            for ev in line.events:
                name = names.get(ev.metadata_id, "")
                if name.startswith(HOST_SPANS):
                    out.append(Event(plane.name, "host", name,
                                     t0 + ev.offset_ps / 1e3,
                                     ev.duration_ps / 1e3))
    return out


def load_events(path: str) -> List[Event]:
    """Events saved as a JSON list of their fields (gzipped if ``.gz``),
    as the recorded chip traces under ``tests/bench`` are."""
    with (gzip.open(path, "rt") if path.endswith(".gz") else open(path)) as f:
        return [Event(**e) for e in json.load(f)]


def scope_path(op_name: str) -> List[str]:
    """'jit(step)/update/mul:' -> ['jit(step)', 'update', 'mul']."""
    return op_name.rsplit(":", 1)[0].split("/") if op_name else []


def _words(component: str) -> List[str]:
    # 'transpose(jvp(drafter))' -> ['transpose', 'jvp', 'drafter']
    return re.findall(r"[^()]+", component)


def under(op_name: str, programs: Sequence[str],
          scopes: Sequence[str]) -> bool:
    """Whether an op of one of ``programs`` runs inside one of ``scopes``:
    a scope names a path component between the program and the op's own
    primitive (transforms such as ``transpose(jvp(...))`` seen through)."""
    path = scope_path(op_name)
    if len(path) < 3 or path[0] not in {f"jit({p})" for p in programs}:
        return False
    return any(w in scopes for c in path[1:-1] for w in _words(c))


def _clip(intervals, w0, w1):
    return [(max(a, w0), min(b, w1)) for a, b in intervals
            if b > w0 and a < w1]


def _length(union) -> float:
    return sum(b - a for a, b in union)


class Spans:
    """One traced window: the first chip's ops and modules, the host
    spans, and what the metric readers ask of them."""

    def __init__(self, events: Sequence[Event],
                 window_span: str = WINDOW_SPAN):
        self.window = TR.window_from_spans(events, window_span)
        w0, w1 = self.window
        planes = sorted({e.plane for e in events
                         if TR.DEVICE_PLANE.match(e.plane)},
                        key=lambda p: int(TR.DEVICE_PLANE.match(p).group(1)))
        first = planes[0] if planes else None
        self.ops = [e for e in events if e.plane == first
                    and e.line == TR.OPS_LINE and e.end_ns > w0
                    and e.start_ns < w1]
        self.modules = [e for e in events if e.plane == first
                        and e.line == TR.MODULES_LINE
                        and w0 <= e.start_ns < w1]
        self.host = sorted((e for e in events if e.line == "host"
                            and e.name != window_span and e.end_ns > w0
                            and e.start_ns < w1),
                           key=lambda e: (e.start_ns, -e.dur_ns))

    @property
    def instrumented(self) -> bool:
        """Whether the program wrote its own spans in the window."""
        return any(e.name.startswith(PROGRAM_SPANS[:2]) for e in self.host)

    def calls(self, kind: str) -> int:
        """Calls of one ``bench/trace.PROGRAMS`` kind that began in the
        window."""
        return sum(1 for e in self.modules
                   if TR.program_name(e.name) in TR.PROGRAMS[kind])

    def program_s(self, names: Sequence[str]) -> float:
        """Device seconds of the modules of the named programs."""
        return sum(e.dur_ns for e in self.modules
                   if TR.program_name(e.name) in names) / 1e9

    def scoped_s(self, programs: Sequence[str],
                 scopes: Sequence[str]) -> float:
        """Device seconds in the window in which an op of ``programs``
        under one of ``scopes`` ran (the union of their intervals: ops of
        a loop body nest inside the loop's own event)."""
        w0, w1 = self.window
        return _length(TR._union(_clip(
            [(e.start_ns, e.end_ns) for e in self.ops
             if under(e.scope, programs, scopes)], w0, w1))) / 1e9

    def stretches_s(self, after: str, before: str,
                    less: Sequence[str] = ()) -> List[float]:
        """Host seconds from the end of each ``after`` span to the start
        of the next ``before`` span, less the time spent inside ``less``
        spans in between; stretches that end outside the window are left
        out."""
        w0, w1 = self.window
        starts = sorted(e.start_ns for e in self.host if e.name == before)
        inner = [e for e in self.host if e.name in less]
        out = []
        for e in self.host:
            if e.name != after or e.end_ns < w0:
                continue
            i = bisect.bisect_left(starts, e.end_ns)
            if i == len(starts) or starts[i] > w1:
                continue
            a, b = e.end_ns, starts[i]
            sub = sum(TR._overlap(a, b, s.start_ns, s.end_ns) for s in inner)
            out.append((b - a - sub) / 1e9)
        return out

    def idle_gaps(self, top: int = 10) -> List[Tuple[float, float, list]]:
        """The first chip's longest idle gaps in the window: (seconds, start
        from the window's start in seconds, [(span, seconds), ...] the
        innermost program span over each part of the gap, longest
        first)."""
        w0, w1 = self.window
        busy = TR._union(_clip([(e.start_ns, e.end_ns) for e in self.ops],
                               w0, w1))
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        gaps = sorted(((b - a, a, b) for a, b in zip(edges[0::2], edges[1::2])
                       if b > a), reverse=True)[:top]
        spans = [e for e in self.host if e.name.startswith(PROGRAM_SPANS)]
        out = []
        for dur, a, b in gaps:
            share: Dict[str, float] = {}
            inside = [s for s in spans if s.end_ns > a and s.start_ns < b]
            cuts = sorted({a, b} | {t for s in inside
                                    for t in (s.start_ns, s.end_ns)
                                    if a < t < b})
            for x, y in zip(cuts, cuts[1:]):
                live = [s for s in inside
                        if s.start_ns <= x and s.end_ns >= y]
                # on one thread the span begun last is the innermost
                name = (max(live, key=lambda s: s.start_ns).name if live
                        else "no program span")
                share[name] = share.get(name, 0.0) + (y - x) / 1e9
            out.append((dur / 1e9, (a - w0) / 1e9,
                        sorted(share.items(), key=lambda kv: -kv[1])))
        return out


def log_gaps(sp: Spans, top: int = 10) -> None:
    for dur, at, parts in sp.idle_gaps(top):
        H.log(f"idle gap {1e3 * dur:.3f} ms at +{at:.3f} s: " + ", ".join(
            f"{n} {1e3 * s:.3f}" for n, s in parts[:4]))


def of_run(run) -> Optional[Spans]:
    """The traced window of ``run``, or None where the run was not traced
    or the program wrote no spans of its own. The trace under
    ``TRACE_DIR`` is parsed once and kept on the run (``run.spans``), where
    a test may also hand one in."""
    if run.trace is None:
        return None
    if getattr(run, "spans", None) is None:
        run.spans = Spans(events_from_xplane(
            TR.find_xplane(str(H.ROOT / TRACE_DIR))))
        log_gaps(run.spans)
    return run.spans if run.spans.instrumented else None
