"""The program's engine spans in a traced run's profiler trace.

With spans on, ``ServeEngine`` writes each phase of a step into the
profiler's host timeline, named by the span's component (``engine.step``,
``engine.schedule``, ``engine.decode.fetch``, ``engine.sample``, ...), on
the clock of the device's ``XLA Ops`` events.  This module reads those
host events once per run from the run's ``.xplane.pb`` and splits the
device's idle time in the traced window by the innermost engine span
open at each instant.  A program that writes no such event gives
nothing, and the readers built on it return None.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import trace as trace_mod

PREFIX = "engine."
STEP = "engine.step"
OUTSIDE = "outside engine.step"


@dataclasses.dataclass
class Phases:
    n_steps: int                     # engine.step spans that start in the window
    idle_ns: Dict[str, float]        # device idle by innermost open engine span,
                                     # averaged over chips; OUTSIDE: none open
    durations: Dict[str, List[float]]  # ns of each span inside the window

    @property
    def step_idle_ns(self) -> float:
        return sum(ns for name, ns in self.idle_ns.items() if name != OUTSIDE)

    def median_ms(self, name: str) -> Optional[float]:
        values = self.durations.get(name)
        return float(np.median(values)) / 1e6 if values else None


def host_events(path: str) -> List[trace_mod.Event]:
    """The ``engine.*`` events on the host planes of the trace at ``path``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [trace_mod.Event(e.name, float(e.start_ns),
                                        float(e.duration_ns), {})
                        for e in line.events if e.name.startswith(PREFIX)]
    return out


def _innermost(spans: Sequence[trace_mod.Event], lo: float, hi: float
               ) -> List[Tuple[float, float, str]]:
    """[lo, hi) cut at every span edge, each piece labelled with the
    shortest span open over it (spans nest), or OUTSIDE."""
    edges = sorted({lo, hi} | {t for s in spans for t in (s.start_ns, s.end_ns)
                               if lo < t < hi})
    pieces = []
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        open_ = [s for s in spans if s.start_ns <= mid < s.end_ns]
        label = min(open_, key=lambda s: s.dur_ns).name if open_ else OUTSIDE
        pieces.append((a, b, label))
    return pieces


def _idle(ops: Sequence[trace_mod.Event], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    busy = trace_mod.merge([(max(e.start_ns, lo), min(e.end_ns, hi)) for e in ops
                            if e.end_ns > lo and e.start_ns < hi])
    edges = [lo] + [t for s, e in busy for t in (s, e)] + [hi]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def split(devices: Dict[str, List[trace_mod.Event]],
          spans: Sequence[trace_mod.Event], lo: float, hi: float
          ) -> Optional[Phases]:
    """Idle time of each chip's ``XLA Ops`` line in [lo, hi), by the
    innermost engine span open over it; None without an engine.step."""
    steps = [s for s in spans if s.name == STEP and lo <= s.start_ns < hi]
    if not steps or not devices:
        return None
    pieces = _innermost(spans, lo, hi)
    idle: Dict[str, float] = {}
    for ops in devices.values():
        gaps = _idle(ops, lo, hi)
        i = j = 0
        while i < len(pieces) and j < len(gaps):
            a, b, label = pieces[i]
            c, d = gaps[j]
            overlap = min(b, d) - max(a, c)
            if overlap > 0:
                idle[label] = idle.get(label, 0.0) + overlap / len(devices)
            if b <= d:
                i += 1
            else:
                j += 1
    durations: Dict[str, List[float]] = {}
    for s in spans:
        if lo <= s.start_ns and s.end_ns <= hi:
            durations.setdefault(s.name, []).append(s.dur_ns)
    return Phases(len(steps), idle, durations)


def load() -> Optional[List[trace_mod.Event]]:
    """The engine's host events in the traced run's ``.xplane.pb``."""
    from bench.run import TRACE_DIR

    try:
        return host_events(trace_mod.find_xplane(str(TRACE_DIR)))
    except FileNotFoundError:
        return None


def of_run(run) -> Optional[Phases]:
    """The run's ``Phases``, read once and kept on ``run``; None without a
    device trace or an engine span in its window."""
    cache = vars(run)
    if "engine_phases" not in cache:
        phases = None
        if run.trace is not None:
            spans = load()
            if spans:
                lo, hi = trace_mod.window(run.trace.trace)
                phases = split(run.trace.trace.devices, spans, lo, hi)
        cache["engine_phases"] = phases
    return cache["engine_phases"]
