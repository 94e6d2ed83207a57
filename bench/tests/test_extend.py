"""A new cell, traffic mix, per-layer metric, configuration and block
family are added with new files and new ``BENCHMARK.json`` entries only:
these tests make them in a copy of the benchmark and run the new cell (at
smoke widths on the CPU) without a line of the harness changed."""
import json
import shutil
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, spec, weights

DATA = Path(__file__).parent / "data"

READER = '''"""Mean decode rows per decode step in the window.  Moves itl_p50_ms."""


def read(run):
    evs = run.step_events("decode")
    if not evs:
        return None
    return sum(ev.batch for ev, _ in evs) / len(evs)
'''

MOE = "deepseek-moe-16b-x"
MOE_CELL = f"{MOE}.batch"
MOE_CONFIG = {
    "name": MOE, "registry": "deepseek-moe-16b", "source": "arXiv:2401.06066",
    "blocks": "moe_fixture", "n_layers": 5,
    # served in float32: at smoke widths a bfloat16 router flips top-2 of 8
    # experts on some seeds, far outside any limit the fault would fail
    "dtype": "float32",
    "moe": {"n_routed_experts": 16, "norm_topk": True},
    "reduced": ["n_layers", "moe"],
    "deployment": "each MoE layer's 64 experts over 4 chips; 16 held here",
}


def copy_bench(tmp_path):
    """A copy of the benchmark under ``tmp_path`` and its BENCHMARK.json."""
    shutil.copytree(spec.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return spec.load_json(spec.ROOT / "BENCHMARK.json")


def add_moe_files(tmp_path, bm):
    """The MoE configuration, its block module, a closed mix and a cell."""
    bench = tmp_path / "bench"
    bm["configs"].append({"name": MOE, "source": "https://arxiv.org/abs/2401.06066",
                          "file": f"bench/configs/{MOE}.json",
                          "reduced": ["n_layers", "moe"],
                          "why": "sparse experts after a dense first layer"})
    bm["workloads"].append({"name": MOE_CELL, "config": MOE, "traffic": "batch",
                            "chips": 1, "why": "closed-loop callers"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    (bench / "configs" / f"{MOE}.json").write_text(json.dumps(MOE_CONFIG))
    shutil.copy(DATA / "moe_fixture.py", bench / "blocks" / "moe_fixture.py")
    (bench / "traffic" / "batch.json").write_text(json.dumps({
        "arrivals": "closed", "period": 8, "granule": 16, "size_seed": 5,
        "prompt": {"median": 32, "sigma": 0.5, "min": 16, "max": 64},
        "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16}}))
    (bench / "cells" / f"{MOE_CELL}.json").write_text(json.dumps({
        "max_batch": 4, "max_seq": 128, "page_size": 16, "prefill_chunk": 32,
        "num_pages": 65, "callers": 6, "ramp_s": 1.0,
        "check_requests": 3, "gap_limit": 0.05}))
    return spec.load_cell(MOE_CELL, tmp_path)


def run(cell, tmp_path, seed=9, seconds=3.0, trace=False):
    return harness.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                            devices=jax.devices()[:1],
                            t_process=time.perf_counter(),
                            trace_dir=tmp_path / ".bench_trace",
                            trace_seconds=1.0, smoke=True)


def test_new_cell_mix_and_metric_from_files_only(tmp_path):
    bm = copy_bench(tmp_path)
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
    out = run(cell, tmp_path, trace=True)
    assert out["metrics"]["sched.decode_rows"]["unit"] == "rows"
    assert out["metrics"]["sched.decode_rows"]["value"] > 0
    # metrics that list other cells in their workloads stay out
    assert set(out["metrics"]) == {"sched.decode_rows"}
    assert out["compared"]["requests_checked"]["value"] >= 1


def test_moe_configuration_under_closed_callers_from_files_only(tmp_path):
    cell = add_moe_files(tmp_path, copy_bench(tmp_path))
    out = run(cell, tmp_path)
    assert out["correct"], out["compared"]
    # the end-to-end metrics that list no cells apply to every cell
    assert set(out["metrics"]) == {"itl_p50_ms", "setup_s"}
    assert out["compared"]["requests_checked"]["value"] == 3


def test_moe_fault_shared_experts_left_out_is_not_correct(tmp_path, monkeypatch):
    cell = add_moe_files(tmp_path, copy_bench(tmp_path))
    monkeypatch.setattr(cell.blocks, "shared_experts",
                        lambda f, h, cfg, fp8: jnp.zeros_like(h))
    out = run(cell, tmp_path)
    assert not out["correct"], out["compared"]
    assert out["compared"]["served_gap"]["value"] > \
        out["compared"]["served_gap"]["limit"]


def test_moe_block_module_matches_the_program_in_float32(tmp_path):
    import dataclasses

    from repro.models.model import LM
    from repro.serve import ServeEngine

    cell = add_moe_files(tmp_path, copy_bench(tmp_path))
    cfg = dataclasses.replace(spec.arch_config(cell.config, smoke=True),
                              dtype="float32")
    assert cfg.first_k_dense == 1 and cfg.n_periods == 1
    w = weights.make_weights(cell.blocks, cfg, 2**33 + 7)
    lm = LM(cfg, ServeEngine.default_runtime(16))
    weights.check_layout(cell.blocks, cfg, lm.param_shapes())
    toks = np.random.default_rng(1).integers(0, cfg.vocab_size, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(lm.prefill)(w, jnp.asarray(toks)[None])
        ref = cell.blocks.forward(w, jnp.asarray(toks), cfg)
    a, b = np.asarray(logits[0]), np.asarray(ref[-1])
    assert np.abs(a - b).max() / np.abs(b).max() < 1e-5


def test_nested_sub_config_sizes_apply_to_the_registry_entry():
    cfg = spec.arch_config(MOE_CONFIG)
    assert cfg.moe.n_routed_experts == 16
    # every other field stays the registry entry's
    assert (cfg.moe.n_shared_experts, cfg.moe.top_k, cfg.moe.expert_d_ff) == \
        (2, 6, 1408)
    assert (cfg.n_layers, cfg.first_k_dense, cfg.n_periods) == (5, 1, 4)
    smoke = spec.arch_config(MOE_CONFIG, smoke=True)
    assert smoke.moe.n_routed_experts == 8  # smoke keeps its widths
    bad = dict(MOE_CONFIG, moe={"n_experts": 16})
    with pytest.raises(SystemExit, match="n_experts"):
        spec.arch_config(bad)
