"""The trace reduction, on synthetic events and on a small trace recorded
on a TPU v5e (``record_trace.py``: three Pallas paged-decode calls and a
bf16 matmul inside ``bench.window``)."""
from pathlib import Path

import pytest

from bench import trace as T

FIXTURE = Path(__file__).with_name("data") / "paged_decode.xplane.pb"


def ev(name, s, e, **stats):
    return T.Event(name, float(s), float(e - s), stats)


def synthetic():
    dev = [ev("%while.1 = (s32[]) while(s32[] %t)", 0, 10),
           ev("%fusion.2 = f32[8] fusion(f32[8] %a)", 2, 6),
           ev("%fusion.3 = f32[8] fusion(f32[8] %b)", 10, 15),
           ev('%call.4 = bf16[8] custom-call(bf16[8] %q), '
              'custom_call_target="tpu_custom_call"', 20, 30)]
    host = [ev("bench.window", 0, 40), ev("bench.step", 0, 18),
            ev("bench.wait", 28, 40)]
    modules = [ev("jit_decode_step_paged(1)", 0, 31)]
    return T.Trace({"/device:TPU:0": dev}, host, {"/device:TPU:0": modules})


def test_merge_and_busy():
    assert T.merge([(5, 15), (0, 10), (20, 30)]) == [(0, 15), (20, 30)]
    t = synthetic()
    assert T.busy_ns(t.devices["/device:TPU:0"], 0, 40) == 25
    assert T.busy_ns(t.devices["/device:TPU:0"], 8, 25) == 12
    assert T.op_label(t.devices["/device:TPU:0"][0]) == "while.1 while"


def test_summary_window_gaps_and_kernel():
    s = T.summarize(synthetic())
    assert s.window_s == pytest.approx(40e-9)
    assert s.busy_s == pytest.approx(25e-9)
    # gaps 15-20 (inside bench.step? no: step ends at 18, so the middle
    # 17.5 is in bench.step) and 30-40 (middle 35, in bench.wait)
    assert s.idle_gaps == [("bench.wait", pytest.approx(10e-9)),
                           ("bench.step", pytest.approx(5e-9))]
    assert s.kernel_s("jit_decode_step_paged") == pytest.approx(10e-9)
    assert s.kernel_s("jit_prefill_chunk") == 0
    # self time: the while's 10 ns less the 4 ns of fusion.2 nested in it
    assert dict(s.device_ops) == {
        "call.4 custom-call tpu_custom_call": pytest.approx(10e-9),
        "fusion.3 fusion": pytest.approx(5e-9),
        "while.1 while": pytest.approx(6e-9),
        "fusion.2 fusion": pytest.approx(4e-9)}


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_recorded_trace():
    s = T.summarize(T.load(str(FIXTURE)))
    assert s.n_chips == 1
    assert 0 < s.busy_s <= s.window_s
    k = s.kernel_s("jit__lambda")
    assert 0 < k <= s.busy_s
    assert len(T.kernel_events(s.trace, "jit__lambda")) == 3
    assert s.device_ops[0][0].endswith("custom-call tpu_custom_call")
    assert [name for name, _ in s.idle_gaps]
