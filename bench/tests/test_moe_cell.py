"""The DeepSeekMoE cell's configuration at smoke widths, with a share of the
routed experts held, served through ``ServeEngine`` (chunked prefill, paged
decode) against ``bench/blocks/moe.py``; broken programs come out not
correct; ``bench/costs/moe.py`` against counts by hand.

The cell runs in a copy of the benchmark at a small geometry under the
cell's own name, judged at the cell's own ``gap_limit``.  It is served in
float32: at smoke widths a bfloat16 router flips the top-2 of 8 experts on
some seeds (``test_extend.py``)."""
import dataclasses
import json
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, spec
from bench.costs import moe as moe_costs
from repro.models import moe as moe_mod

CELL = "deepseek-moe-16b-5l.batch"
CONFIG = "deepseek-moe-16b-5l"
HELD = 4  # of the smoke model's 8 routed experts


def cell_config(config):
    return spec.load_json(spec.ROOT / "bench" / "configs" / f"{config}.json")


@pytest.fixture
def cell(tmp_path, monkeypatch):
    """The cell in a copy of the benchmark, at a small geometry and mix; its
    configuration at smoke widths holds HELD experts, in float32."""
    shutil.copytree(spec.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    real = spec.load_json(spec.ROOT / "bench" / "cells" / f"{CELL}.json")
    (tmp_path / "bench" / "cells" / f"{CELL}.json").write_text(json.dumps({
        "max_batch": 4, "max_seq": 128, "page_size": 16, "prefill_chunk": 32,
        "num_pages": 65, "callers": 6, "ramp_s": 1.0, "check_requests": 3,
        "gap_limit": real["gap_limit"]}))
    (tmp_path / "bench" / "traffic" / "batch.json").write_text(json.dumps({
        "arrivals": "closed", "period": 8, "granule": 16, "size_seed": 5,
        "prompt": {"median": 32, "sigma": 0.5, "min": 16, "max": 64},
        "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16}}))
    arch_config = spec.arch_config

    def held_smoke(config, smoke=False):
        cfg = arch_config(config, smoke)
        if not smoke:
            return cfg
        return dataclasses.replace(cfg, dtype="float32", moe=dataclasses.replace(
            cfg.moe, held_experts=HELD))

    monkeypatch.setattr(spec, "arch_config", held_smoke)
    return spec.load_cell(CELL, tmp_path)


def run(cell, tmp_path, seed=2**32 + 11):
    return harness.run_cell(cell, seed=seed, seconds=3.0, trace=False,
                            devices=jax.devices()[:1],
                            t_process=time.perf_counter(),
                            trace_dir=tmp_path / ".bench_trace",
                            trace_seconds=0.0, smoke=True)


def test_engine_matches_the_reference_in_float32(cell):
    from bench.weights import check_layout, make_weights
    from repro.serve import ServeEngine

    cfg = spec.arch_config(cell.config, smoke=True)
    assert cfg.moe.n_held == HELD < cfg.moe.n_routed_experts
    assert cfg.first_k_dense == 1 and cfg.n_periods == 1
    w = make_weights(cell.blocks, cfg, 2**33 + 5)
    eng = harness.engine_class(cfg)(
        cfg.name, smoke=True, max_batch=2, page_size=8, max_seq=96,
        params=w, prefill_chunk=16, collect_logits=True)
    check_layout(cell.blocks, cfg, eng.lm.param_shapes())
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (37, 20)]
    reqs = [eng.submit(p, 6) for p in prompts]
    eng.run()
    with jax.default_matmul_precision("highest"):
        for p, r in zip(prompts, reqs):
            seq = np.concatenate([p, np.asarray(r.generated[:-1], np.int32)])
            ref = np.asarray(cell.blocks.forward(w, jnp.asarray(seq), cfg))
            want = ref[len(p) - 1:]
            got = np.stack(r.logits_trace)
            assert got.shape == want.shape
            assert np.abs(got - want).max() / np.abs(want).max() < 1e-5


def test_sound_cell_is_correct(cell, tmp_path):
    out = run(cell, tmp_path)
    assert out["correct"], out["compared"]
    assert set(out["metrics"]) == {"itl_p50_ms", "setup_s"}
    assert out["compared"]["requests_checked"]["value"] == 3


def _no_shared_experts(xt, sh_g, sh_u, sh_d, dtype):
    return jnp.zeros_like(xt, dtype=jnp.dtype(dtype))


def _every_expert_held(route):
    """A router over the held experts alone, as though they were all."""
    def held_only(p, x, cfg, train):
        return route({**p, "router": p["router"][:, :cfg.moe.n_held]}, x,
                     cfg, train)
    return held_only


@pytest.mark.parametrize("fault", ["shared_experts_left_out",
                                   "all_experts_as_if_held"])
def test_broken_program_is_not_correct(cell, tmp_path, monkeypatch, fault):
    if fault == "shared_experts_left_out":
        monkeypatch.setattr(moe_mod, "_shared_ffn", _no_shared_experts)
    else:
        monkeypatch.setattr(moe_mod, "_route", _every_expert_held(moe_mod._route))
    out = run(cell, tmp_path)
    assert not out["correct"], out["compared"]
    assert out["compared"]["served_gap"]["value"] > \
        out["compared"]["served_gap"]["limit"]


def test_configuration_keeps_every_published_width():
    cfg = spec.arch_config(cell_config(CONFIG))
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == \
        (2048, 16, 16, 128)
    assert (cfg.d_ff, cfg.vocab_size, cfg.first_k_dense) == (10944, 102400, 1)
    m = cfg.moe
    assert (m.n_routed_experts, m.top_k, m.expert_d_ff, m.n_shared_experts) == \
        (64, 6, 1408, 2)
    assert not m.norm_topk
    # the cuts, as listed in reduced
    assert (cfg.n_layers, m.n_held) == (5, 16)
    assert cell_config(CONFIG)["reduced"] == ["n_layers", "moe.held_experts"]


# --------------------------------------------------------------- cost counts
def test_decode_flops_one_row():
    cfg = spec.arch_config(cell_config(CONFIG))
    # per token: the dense layer 16,777,216 attention + 67,239,936 FFN; each
    # MoE layer 16,777,216 attention + 131,072 router + 17,301,504 shared
    # + 1.5 held experts (6 * 16 / 64) of 8,650,752 = 47,185,920; the head
    # 209,715,200; attention over 100 keys: 5 * 4 * 16 * 128 * 100
    per_token = 2 * (84_017_152 + 4 * 47_185_920 + 209_715_200)
    assert moe_costs.decode_flops(cfg, [100]) == per_token + 4_096_000


def test_decode_bytes_one_row():
    cfg = spec.arch_config(cell_config(CONFIG))
    # one row touches 16 * (1 - 58/64) = 1.5 held experts a layer;
    # params: dense layer 84,017,152 + 4,096 norms; each MoE layer 4,096
    # norms + 34,209,792 always + 12,976,128 touched; head 209,715,200,
    # final norm and one embedding row 2,048 each; at 2 bytes.
    # K/V: 5 * (2*16*128*100*2 read + 2*16*128*2 written)
    params = 84_021_248 + 4 * 47_190_016 + 209_715_200 + 4_096
    want = params * 2 + 5 * (819_200 + 8_192)
    assert moe_costs.decode_bytes(cfg, [100]) == pytest.approx(want, rel=1e-12)


def test_a_full_decode_batch_reads_nearly_every_held_expert():
    cfg = spec.arch_config(cell_config(CONFIG))
    assert moe_costs.touched_experts(cfg, 64) == pytest.approx(
        16 * (1 - (58 / 64) ** 64))
    assert 15.9 < moe_costs.touched_experts(cfg, 64) < 16
    # 1.97 GB of bf16 weights at 64 rows (no K/V): 56% of it held experts
    weights = moe_costs.decode_bytes(cfg, [0] * 64) - 5 * 2 * 16 * 128 * 64 * 2
    assert 1.96e9 < weights < 1.98e9
    held = 4 * moe_costs.touched_experts(cfg, 64) * 8_650_752 * 2
    assert 0.55 < held / weights < 0.57
