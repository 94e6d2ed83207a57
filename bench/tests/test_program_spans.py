"""The engine-span readers (``engine.idle_ms_per_step``, ``engine.sample_ms``,
``engine.fetch_ms``) on synthetic ``XLA Ops`` and ``engine.*`` host events
with known gaps, and ``program_spans.host_events`` on a profile written on
the CPU."""
import pytest

from bench import harness, program_spans, spec
from bench import trace as T

MS = 1e6  # ns


def ev(name, s, e):
    return T.Event(name, s * MS, (e - s) * MS, {})


# two engine steps in a 52 ms window; the device runs [3, 16) and [29, 41)
SPANS = [
    ev("engine.step", 0, 24), ev("engine.schedule", 0, 2),
    ev("engine.decode", 2, 20), ev("engine.decode.launch", 2, 4),
    ev("engine.decode.wait", 4, 16), ev("engine.decode.fetch", 16, 20),
    ev("engine.sample", 20, 23),
    ev("engine.step", 26, 50), ev("engine.schedule", 26, 28),
    ev("engine.decode", 28, 44), ev("engine.decode.launch", 28, 29),
    ev("engine.decode.wait", 29, 41), ev("engine.decode.fetch", 41, 44),
    ev("engine.sample", 44, 49), ev("engine.retire", 49, 50),
]
OPS = [ev("%fusion.1 = f32[8] fusion(f32[8] %a)", 3, 16),
       ev("%fusion.2 = f32[8] fusion(f32[8] %b)", 29, 41)]
# idle [0, 3), [16, 29), [41, 52), by innermost span
IDLE = {"engine.schedule": 4, "engine.decode.launch": 2, "engine.decode.fetch": 7,
        "engine.sample": 8, "engine.retire": 1, "engine.step": 1,
        program_spans.OUTSIDE: 4}


class Run:
    def __init__(self, summary):
        self.trace = summary
        self.notes = []

    def note(self, msg):
        self.notes.append(msg)


def traced_run(devices=None):
    trace = T.Trace(devices or {"/device:TPU:0": OPS}, [ev("bench.window", 0, 52)])
    return Run(T.summarize(trace))


def read(name, run):
    return harness.load_reader(spec.ROOT, name)(run)


def test_split_by_innermost_span():
    p = program_spans.split({"/device:TPU:0": OPS}, SPANS, 0, 52 * MS)
    assert p.n_steps == 2
    assert {k: v / MS for k, v in p.idle_ns.items()} == pytest.approx(IDLE)
    assert p.step_idle_ns / MS == pytest.approx(23)
    assert p.median_ms("engine.sample") == pytest.approx(4.0)
    assert p.median_ms("engine.verify") is None


def test_split_averages_over_chips():
    both = {"/device:TPU:0": OPS, "/device:TPU:1": [ev("op", 0, 52)]}
    p = program_spans.split(both, SPANS, 0, 52 * MS)
    assert p.step_idle_ns / MS == pytest.approx(23 / 2)


def test_readers_values_and_note(monkeypatch):
    monkeypatch.setattr(program_spans, "load", lambda: SPANS)
    run = traced_run()
    assert read("engine.idle_ms_per_step", run) == pytest.approx(11.5)
    assert read("engine.sample_ms", run) == pytest.approx(4.0)
    assert read("engine.fetch_ms", run) == pytest.approx(3.5)
    note = run.notes[0]
    # 23 of the window's 27 ms of idle inside engine.step; 22 of 23 in a child
    assert "85.2% of the window's idle" in note and "95.7% of that" in note
    assert "engine.sample 4.000" in note and "engine.decode.launch 1.000" in note


def test_spans_are_read_once_per_run(monkeypatch):
    calls = []
    monkeypatch.setattr(program_spans, "load", lambda: calls.append(1) or SPANS)
    run = traced_run()
    for name in ("engine.idle_ms_per_step", "engine.sample_ms", "engine.fetch_ms"):
        read(name, run)
    assert len(calls) == 1


@pytest.mark.parametrize("name", ["engine.idle_ms_per_step", "engine.sample_ms",
                                  "engine.fetch_ms"])
@pytest.mark.parametrize("case", ["no trace", "no trace file", "no engine span",
                                  "no step in the window"])
def test_readers_return_none_without_spans(monkeypatch, name, case):
    spans = {"no trace file": None, "no engine span": [],
             "no step in the window": [ev("engine.step", 60, 70)]}.get(case, SPANS)
    monkeypatch.setattr(program_spans, "load", lambda: spans)
    run = Run(None) if case == "no trace" else traced_run()
    assert read(name, run) is None


def test_host_events_from_a_profile(tmp_path):
    import jax

    from repro.telemetry.trace import SpanTracer

    tracer = SpanTracer(trace=("bench",))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("step", component="engine.step"):
            with tracer.span("sample", component="engine.sample"):
                pass
    finally:
        jax.profiler.stop_trace()
    got = program_spans.host_events(T.find_xplane(str(tmp_path)))
    by_name = {e.name: e for e in got}
    assert set(by_name) == {"engine.step", "engine.sample"}
    step, sample = by_name["engine.step"], by_name["engine.sample"]
    assert step.start_ns <= sample.start_ns and sample.end_ns <= step.end_ns
