"""The plain reference against the program's own forward pass, both in
float32 at ``highest`` (the configuration's dtype switched to float32, at
the repo's smoke widths): they must agree to float32 rounding, so every
gap the benchmark reads on the chip is the program's bfloat16 serving
path, not a difference of equations."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec, weights
from bench.blocks import dense


@pytest.mark.parametrize("name", ["stablelm-1.6b"])
def test_reference_matches_program_in_float32(name):
    from repro.models.model import LM
    from repro.serve import ServeEngine

    conf = spec.load_json(spec.ROOT / "bench" / "configs" / f"{name}.json")
    cfg = dataclasses.replace(spec.arch_config(conf, smoke=True), dtype="float32")
    w = weights.make_weights(dense, cfg, 2**33 + 5)
    lm = LM(cfg, ServeEngine.default_runtime(16))
    weights.check_layout(dense, cfg, lm.param_shapes())
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(lm.prefill)(w, jnp.asarray(toks)[None])
        ref = dense.forward(w, jnp.asarray(toks), cfg)
    a, b = np.asarray(logits[0]), np.asarray(ref[-1])
    assert np.abs(a - b).max() / np.abs(b).max() < 1e-5


def test_reference_is_causal_under_padding():
    conf = spec.load_json(spec.ROOT / "bench" / "configs" / "stablelm-1.6b.json")
    cfg = spec.arch_config(conf, smoke=True)
    w = weights.make_weights(dense, cfg, 7)
    toks = np.arange(24, dtype=np.int32) % cfg.vocab_size
    padded = np.concatenate([toks, np.zeros(40, np.int32)])
    with jax.default_matmul_precision("highest"):
        a = np.asarray(dense.forward(w, jnp.asarray(toks), cfg))
        b = np.asarray(dense.forward(w, jnp.asarray(padded), cfg))[:24]
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_dense_block_module_gives_the_moved_reference_value_for_value():
    """``bench/blocks/dense.py`` holds the reference that was
    ``bench/reference.py``; its logits (f32 and the fp8 control) at smoke
    widths equal those that module gave, frozen in
    ``data/dense_reference_smoke.npz``, bit for bit."""
    golden = np.load(spec.ROOT / "bench" / "tests" / "data" /
                     "dense_reference_smoke.npz")
    cell = spec.load_cell("stablelm-1.6b.chat")
    cfg = spec.arch_config(cell.config, smoke=True)
    w = weights.make_weights(cell.blocks, cfg, int(golden["seed"]))
    toks = jnp.asarray(golden["tokens"])
    with jax.default_matmul_precision("highest"):
        for precision in ("f32", "fp8"):
            got = np.asarray(cell.blocks.forward(w, toks, cfg, precision))
            np.testing.assert_array_equal(got, golden[precision])
