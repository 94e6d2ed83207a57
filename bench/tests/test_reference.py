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

from bench import reference, spec, weights


@pytest.mark.parametrize("name", ["stablelm-1.6b"])
def test_reference_matches_program_in_float32(name):
    from repro.models.model import LM
    from repro.serve import ServeEngine

    conf = spec.load_json(spec.ROOT / "bench" / "configs" / f"{name}.json")
    cfg = dataclasses.replace(spec.arch_config(conf, smoke=True), dtype="float32")
    w = weights.make_weights(cfg, 2**33 + 5)
    lm = LM(cfg, ServeEngine.default_runtime(16))
    weights.check_layout(cfg, lm.param_shapes())
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(lm.prefill)(w, jnp.asarray(toks)[None])
        ref = reference.forward(w, jnp.asarray(toks), cfg)
    a, b = np.asarray(logits[0]), np.asarray(ref[-1])
    assert np.abs(a - b).max() / np.abs(b).max() < 1e-5


def test_reference_is_causal_under_padding():
    conf = spec.load_json(spec.ROOT / "bench" / "configs" / "stablelm-1.6b.json")
    cfg = spec.arch_config(conf, smoke=True)
    w = weights.make_weights(cfg, 7)
    toks = np.arange(24, dtype=np.int32) % cfg.vocab_size
    padded = np.concatenate([toks, np.zeros(40, np.int32)])
    with jax.default_matmul_precision("highest"):
        a = np.asarray(reference.forward(w, jnp.asarray(toks), cfg))
        b = np.asarray(reference.forward(w, jnp.asarray(padded), cfg))[:24]
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
