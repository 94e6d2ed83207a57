"""A new cell, traffic mix and per-layer metric are added with new files
and new ``BENCHMARK.json`` entries only: this test makes them in a copy of
the benchmark and runs the new cell (at smoke widths on the CPU) without a
line of the harness changed."""
import json
import shutil
import time

import jax

from bench import harness, spec

READER = '''"""Mean decode rows per decode step in the window.  Moves itl_p50_ms."""


def read(run):
    evs = run.step_events("decode")
    if not evs:
        return None
    return sum(ev.batch for ev, _ in evs) / len(evs)
'''


def test_new_cell_mix_and_metric_from_files_only(tmp_path):
    shutil.copytree(spec.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bm = spec.load_json(spec.ROOT / "BENCHMARK.json")
    name = "stablelm-1.6b.short"
    bm["workloads"].append({"name": name, "config": "stablelm-1.6b",
                            "traffic": "short", "chips": 1,
                            "why": "short distinct prompts"})
    bm["per_layer"].append({"name": "sched.decode_rows", "unit": "rows",
                            "better": "higher", "source": "program_counter",
                            "layer": "scheduler", "moves": "itl_p50_ms",
                            "workloads": [name]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    (tmp_path / "bench" / "traffic" / "short.json").write_text(json.dumps({
        "arrivals": "periodic_poisson", "period": 8, "granule": 16,
        "size_seed": 3,
        "prompt": {"median": 32, "sigma": 0.5, "min": 16, "max": 64},
        "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16}}))
    (tmp_path / "bench" / "cells" / f"{name}.json").write_text(json.dumps({
        "max_batch": 4, "max_seq": 128, "page_size": 16, "prefill_chunk": 32,
        "num_pages": 65, "rate_rps": 4.0, "ramp_s": 1.0,
        "check_requests": 3, "gap_limit": 0.05}))
    (tmp_path / "bench" / "metrics" / "sched.decode_rows.py").write_text(READER)

    cell = spec.load_cell(name, tmp_path)
    out = harness.run_cell(cell, seed=9, seconds=3.0, trace=True,
                           devices=jax.devices()[:1],
                           t_process=time.perf_counter(),
                           trace_dir=tmp_path / ".bench_trace",
                           trace_seconds=1.0, smoke=True)
    assert out["metrics"]["sched.decode_rows"]["unit"] == "rows"
    assert out["metrics"]["sched.decode_rows"]["value"] > 0
    # metrics that list other cells in their workloads stay out
    assert set(out["metrics"]) == {"sched.decode_rows"}
    assert out["compared"]["requests_checked"]["value"] >= 1
