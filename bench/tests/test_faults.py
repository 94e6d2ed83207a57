"""A run with its timed path broken underneath comes out not correct.

Each test drives the whole of a run of the chat cell (without the look for
a chip, at smoke widths on the CPU) with the engine's decode step broken
in one way a serving cell can break: the K/V state left unchanged, half
of the batch left out, or every produced token altered."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness, spec

CELL = "stablelm-1.6b.chat"


def broken_engine_class(kind):
    sound = harness.engine_class

    def engine_class(cfg):
        base = sound(cfg)

        class Broken(base):
            def __init__(self, *a, **k):
                super().__init__(*a, **k)
                decode = self._decode

                def step(params, tokens, lengths, cache, tables):
                    if kind == "state_unchanged":
                        logits, _ = decode(params, tokens, lengths,
                                           jax.tree.map(jnp.copy, cache), tables)
                        return logits, cache
                    logits, cache = decode(params, tokens, lengths, cache, tables)
                    if kind == "half_batch":  # every other live row
                        rows = jnp.arange(logits.shape[0])
                        out = (lengths > 0) & (rows % 2 == 1)
                        logits = jnp.where(out[:, None], 0, logits)
                    elif kind == "token_altered":
                        logits = jnp.roll(logits, 1, axis=-1)
                    return logits, cache

                self._decode = step

        return Broken

    return engine_class


def run(seconds=6.0):
    cell = spec.load_cell(CELL)
    return harness.run_cell(cell, seed=2**31 + 3, seconds=seconds, trace=False,
                            devices=jax.devices()[:1],
                            t_process=time.perf_counter(),
                            trace_dir=spec.ROOT / ".bench_trace",
                            trace_seconds=0.0, smoke=True)


def test_sound_run_is_correct():
    out = run()
    assert out["correct"], out["compared"]


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch", "token_altered"])
def test_fault_is_not_correct(monkeypatch, kind):
    monkeypatch.setattr(harness, "engine_class", broken_engine_class(kind))
    out = run()
    assert not out["correct"], out["compared"]
    assert out["compared"]["served_gap"]["value"] > out["compared"]["served_gap"]["limit"]
