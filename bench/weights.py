"""Seeded weights, made by the benchmark on the device in one jitted call.

The tree is laid out as the program's ``LM`` takes its parameters (an
embedding, ``periods`` stacked on a leading layer axis, ``final_norm``,
``lm_head``); the layout is written
out here from the configuration, not read from the program, and
``check_layout`` refuses a program whose tree differs.  The same seed gives
the same values, so the reference rebuilds them after the program's state
is freed.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _attention(cfg) -> Dict[str, Tuple[int, ...]]:
    d, h, k, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    out = {"wq": (d, h * hd), "wk": (d, k * hd), "wv": (d, k * hd),
           "wo": (h * hd, d)}
    if cfg.qkv_bias:
        out.update(bq=(h * hd,), bk=(k * hd,), bv=(k * hd,))
    if cfg.qk_norm:
        out.update(q_norm=(hd,), k_norm=(hd,))
    return out


def _block(cfg) -> Dict:
    d = cfg.d_model
    ffn = {"w_gate": (d, cfg.d_ff), "w_up": (d, cfg.d_ff),
           "w_down": (cfg.d_ff, d)}
    return {"ln1": (d,), "mixer": _attention(cfg), "ln2": (d,), "ffn": ffn}


def layout(cfg) -> Dict:
    """Shape tree of the parameters (tuples of ints at the leaves)."""
    if any(s.mixer != "attn" or s.ffn != "dense" for s in cfg.period) \
            or len(cfg.period) != 1 or cfg.mla is not None or cfg.first_k_dense:
        raise ValueError(f"{cfg.name}: the benchmark serves attention "
                         "layers with a dense FFN, one per period")
    n = cfg.n_periods
    period = jax.tree.map(lambda s: (n,) + s, _block(cfg),
                          is_leaf=lambda x: isinstance(x, tuple))
    tree = {
        "embed": (cfg.vocab_size, cfg.d_model),
        "final_norm": (cfg.d_model,),
        "periods": {"pos0": period},
    }
    if not cfg.tie_embeddings:
        tree["lm_head"] = (cfg.d_model, cfg.vocab_size)
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def _value(key, path: str, shape: Tuple[int, ...]) -> jnp.ndarray:
    name = path.rsplit("/", 1)[-1]
    if name in ("ln1", "ln2", "final_norm", "q_norm", "k_norm"):
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if name in ("bq", "bk", "bv"):
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if name == "embed":
        fan_in = shape[-1]
    else:  # (..., in, out) matrices, stacked layers in front
        fan_in = shape[-2]
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def _paths(tree, prefix=""):
    """(path, shape) for every leaf, in a fixed order."""
    if _is_shape(tree):
        return [(prefix, tree)]
    items = tree.items() if isinstance(tree, dict) else enumerate(tree)
    out = []
    for k, v in items:
        out += _paths(v, f"{prefix}/{k}")
    return out


def _build(tree, values, prefix=""):
    if _is_shape(tree):
        return values[prefix]
    if isinstance(tree, dict):
        return {k: _build(v, values, f"{prefix}/{k}") for k, v in tree.items()}
    return tuple(_build(v, values, f"{prefix}/{i}") for i, v in enumerate(tree))


def make_weights(cfg, seed: int, device=None) -> Dict:
    """Every parameter from ``seed``, float32, in one jitted call placed on
    ``device`` (the default device when None)."""
    tree = layout(cfg)
    paths = _paths(tree)

    def make(key):
        vals = {p: _value(jax.random.fold_in(key, i), p, s)
                for i, (p, s) in enumerate(paths)}
        return _build(tree, vals)

    out_sh = None
    if device is not None:
        out_sh = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(make, out_shardings=out_sh)(seed_key(seed))


def check_layout(cfg, program_shapes) -> None:
    """Refuse a program whose parameter tree is not the one made here."""
    ours = jax.tree.map(lambda s: tuple(s), layout(cfg), is_leaf=_is_shape)
    theirs = jax.tree.map(lambda s: tuple(s.shape), program_shapes)
    if jax.tree.structure(ours, is_leaf=_is_shape) != \
            jax.tree.structure(theirs, is_leaf=_is_shape) or \
            jax.tree.leaves(ours, is_leaf=_is_shape) != \
            jax.tree.leaves(theirs, is_leaf=_is_shape):
        raise SystemExit(f"{cfg.name}: the program's parameter tree differs "
                         "from the benchmark's layout")
