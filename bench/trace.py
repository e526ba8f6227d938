"""Reduce a profiler trace to device busy time, per-program device time and
the ``breakdown`` of the result line.

A trace is first flattened to plain event records (``events_from_xplane``),
so the reduction (``reduce``) runs on data a test can hold: the recorded
chip trace under ``tests/bench`` is such a list (``load_events``).

Device work is read from the TPU planes (``/device:TPU:<n>``): busy time is
the union of the intervals of the ``XLA Ops`` line, averaged over the
chips; a program's time is the sum of its ``XLA Modules`` events. Host
spans are the benchmark's own ``jax.profiler.TraceAnnotation`` names
(``bench.*``) on any host line; each idle gap of the first device is put
down to the host span that covers most of it.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."
# The names JAX gives the program's jitted entry points today (module
# names are "jit_<function>(<id>)"); the program gives them no stable names
# of their own yet. Each kind is what a per-layer metric reads. The
# engine's decode step is jitted through functools.partial (its greedy
# and sampled twins), which JAX names "_unknown"; nothing else on the
# serving path is.
PROGRAMS = {
    "decode_step": ("_unknown", "_paged_step_impl", "_sched_step_impl",
                    "_step_impl"),
    "prefill": ("_prefill_pad_impl", "_prefill_impl", "_chunk_impl"),
    "train_step": ("step",),
}


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Summary:
    window_s: float                  # traced window, first to last event
    busy_s: float                    # device busy, mean over chips
    chips: int
    programs: Dict[str, Tuple[float, int]] = field(default_factory=dict)
    device_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    def kind_time(self, kind: str) -> Tuple[float, int]:
        """(device seconds, calls) of the programs of one PROGRAMS kind, on
        the first chip."""
        s, n = 0.0, 0
        for name, (sec, calls) in self.programs.items():
            if name in PROGRAMS[kind]:
                s, n = s + sec, n + calls
        return s, n


def program_name(module_event_name: str) -> str:
    """'jit__paged_step_impl(1234)' -> '_paged_step_impl'."""
    name = module_event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def events_from_xplane(path: str) -> List[Event]:
    """Every event of the TPU planes' op and module lines, and the
    benchmark's host spans, from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_SPAN_PREFIX):
                    continue
                out.append(Event(plane.name, line.name if device else "host",
                                 ev.name, float(ev.start_ns),
                                 float(ev.duration_ns)))
    return out


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_events(path: str) -> List[Event]:
    """Events saved as a JSON list of their fields (gzipped if ``.gz``)."""
    with (gzip.open(path, "rt") if path.endswith(".gz") else open(path)) as f:
        return [Event(**e) for e in json.load(f)]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def reduce(events: List[Event], window_span: Optional[str] = None,
           top: int = 10) -> Summary:
    """Busy time, program times, top device ops and longest idle gaps over
    the host span ``window_span`` (default: first to last device event).
    Raises when the trace holds no device op."""
    ops = defaultdict(list)
    for e in events:
        if e.line == OPS_LINE:
            ops[e.plane].append(e)
    if not ops:
        raise ValueError("the trace holds no device operation")
    if window_span is not None:
        window = window_from_spans(events, window_span)
    else:
        window = (min(e.start_ns for es in ops.values() for e in es),
                  max(e.end_ns for es in ops.values() for e in es))
    w0, w1 = window
    busy = {}
    unions = {}
    for plane, es in ops.items():
        u = _union([(max(e.start_ns, w0), min(e.end_ns, w1)) for e in es
                    if e.end_ns > w0 and e.start_ns < w1])
        unions[plane] = u
        busy[plane] = sum(b - a for a, b in u)
    first = min(ops, key=lambda p: int(DEVICE_PLANE.match(p).group(1)))

    progs: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    for e in events:
        if e.plane == first and e.line == MODULES_LINE \
                and w0 <= e.start_ns < w1:
            p = progs[program_name(e.name)]
            p[0] += e.dur_ns / 1e9
            p[1] += 1

    op_time: Dict[str, float] = defaultdict(float)
    for e in ops[first]:
        op_time[e.name] += _overlap(e.start_ns, e.end_ns, w0, w1) / 1e9
    device_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]

    spans = [e for e in events if e.line == "host" and e.name != window_span]
    gaps = []
    edges = [w0] + [x for ab in unions[first] for x in ab] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        best, cover = "no host span", 0.0
        for s in spans:
            c = _overlap(a, b, s.start_ns, s.end_ns)
            if c > cover:
                best, cover = s.name, c
        gaps.append((best, (b - a) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(w1 - w0) / 1e9,
                   busy_s=sum(busy.values()) / len(busy) / 1e9,
                   chips=len(busy),
                   programs={k: (v[0], v[1]) for k, v in progs.items()},
                   device_ops=device_ops, idle_gaps=gaps[:top])


def window_from_spans(events: List[Event], name: str) -> Tuple[float, float]:
    """The (start, end) of the host span ``name``: the benchmark marks its
    traced window with one."""
    for e in events:
        if e.line == "host" and e.name == name:
            return e.start_ns, e.end_ns
    raise ValueError(f"no host span {name!r} in the trace")
