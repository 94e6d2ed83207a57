"""Reduce a profiler trace (``.xplane.pb``) to device busy time, time per
operation, kernel time and the longest idle gaps.

Busy time on a chip is the union of the intervals of the events on its
``XLA Ops`` line (a ``while`` op's event covers its body's ops); idle is
the rest of the traced window.  An operation's time in the breakdown is
its self time, less the ops nested in it.  An idle gap is labelled with
the benchmark's own host annotation (``bench.*``, written with
``jax.profiler.TraceAnnotation``) that was open at the gap's middle, or
``unlabelled``.  On a TPU v5e the op events are named by their HLO text
(``%name = type opcode(...)``) and the ``XLA Modules`` line names the
jitted program each ran in.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, object]

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Event]]   # device plane name -> its XLA ops
    host: List[Event]                 # bench.* host annotations
    modules: Dict[str, List[Event]] = dataclasses.field(default_factory=dict)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line) -> List[Event]:
    return [Event(e.name, float(e.start_ns), float(e.duration_ns),
                  dict(e.stats)) for e in line.events]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, modules = {}, [], {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [e for e in _events(line)
                         if e.name.startswith(HOST_PREFIX)]
    return Trace(devices, host, modules)


def merge(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    """Union of the events' intervals, clipped to [lo, hi)."""
    spans = [(max(e.start_ns, lo), min(e.end_ns, hi)) for e in events]
    return sum(e - s for s, e in merge([x for x in spans if x[1] > x[0]]))


WINDOW = HOST_PREFIX + "window"


def window(trace: Trace) -> Tuple[float, float]:
    """The traced window: the ``bench.window`` host annotation when the run
    wrote one, else first to last device event over all chips."""
    marks = [h for h in trace.host if h.name == WINDOW]
    if marks:
        return marks[0].start_ns, marks[0].end_ns
    evs = [e for d in trace.devices.values() for e in d]
    if not evs:
        raise ValueError("no device operation in the trace")
    return min(e.start_ns for e in evs), max(e.end_ns for e in evs)


_HLO = re.compile(r"^%?([\w.\-]+) = .*? ([a-z][\w\-]*)\(")


def op_label(e: Event) -> str:
    """``name opcode`` from an event's HLO text (``%while.5 = (...)
    while(...)`` -> ``while.5 while``); a Pallas kernel reads
    ``... custom-call tpu_custom_call``.  Other names pass through."""
    m = _HLO.match(e.name)
    if not m:
        return e.name
    label = f"{m.group(1)} {m.group(2)}"
    if 'custom_call_target="tpu_custom_call"' in e.name:
        label += " tpu_custom_call"
    return label


def self_ns(events: Sequence[Event]) -> List[float]:
    """Each event's duration less that of the events nested in it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start_ns, -events[i].dur_ns))
    out = [e.dur_ns for e in events]
    stack: List[int] = []
    for i in order:
        e = events[i]
        while stack and events[stack[-1]].end_ns <= e.start_ns:
            stack.pop()
        if stack:
            out[stack[-1]] -= e.dur_ns
        stack.append(i)
    return out


def top_ops(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The n operations with the most device self seconds, averaged over
    chips."""
    total: Dict[str, float] = defaultdict(float)
    for evs in trace.devices.values():
        for e, ns in zip(evs, self_ns(evs)):
            total[op_label(e)] += ns
    k = max(len(trace.devices), 1)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [(name, ns / k / 1e9) for name, ns in ranked]


def idle_gaps(trace: Trace, lo: float, hi: float,
              n: int = 10) -> List[Tuple[str, float]]:
    """The n longest idle gaps on any chip within [lo, hi), each labelled
    with the innermost bench.* host annotation open at its middle."""
    gaps = []
    for evs in trace.devices.values():
        busy = merge([(max(e.start_ns, lo), min(e.end_ns, hi)) for e in evs
                      if e.end_ns > lo and e.start_ns < hi])
        edges = [lo] + [t for s, e in busy for t in (s, e)] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, b))
    gaps.sort(reverse=True)
    out = []
    for dur, a, b in gaps[:n]:
        mid = (a + b) / 2
        open_ = [h for h in trace.host if h.start_ns <= mid < h.end_ns]
        label = min(open_, key=lambda h: h.dur_ns).name if open_ else "unlabelled"
        out.append((label, dur / 1e9))
    return out


PALLAS = 'custom_call_target="tpu_custom_call"'


def kernel_events(trace: Trace, module: str, pattern: str = PALLAS) -> List[Event]:
    """Device ops whose HLO text contains ``pattern`` and that ran inside a
    program whose module name starts with ``module``."""
    out = []
    for dev, evs in trace.devices.items():
        spans = [(m.start_ns, m.end_ns) for m in trace.modules.get(dev, [])
                 if m.name.startswith(module)]
        for e in evs:
            if pattern in e.name and any(a <= e.start_ns < b for a, b in spans):
                out.append(e)
    return out


@dataclasses.dataclass
class Summary:
    busy_s: float             # averaged over the chips traced
    window_s: float
    n_chips: int
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    trace: Trace

    def kernel_s(self, module: str, pattern: str = PALLAS) -> float:
        return sum(e.dur_ns for e in kernel_events(self.trace, module, pattern)) / 1e9


def summarize(trace: Trace, window_ns: Optional[Tuple[float, float]] = None
              ) -> Summary:
    lo, hi = window_ns or window(trace)
    n = len(trace.devices)
    busy = sum(busy_ns(evs, lo, hi) for evs in trace.devices.values())
    return Summary(
        busy_s=busy / max(n, 1) / 1e9,
        window_s=(hi - lo) / 1e9,
        n_chips=n,
        device_ops=top_ops(trace),
        idle_gaps=idle_gaps(trace, lo, hi),
        trace=trace,
    )
