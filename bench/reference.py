"""Plain reference forward pass: ``jax.numpy`` in float32 at ``highest``
matmul precision, one layer at a time, independent of the program's code.

It computes what the configuration states: pre-norm decoder layers of
RMSNorm, multi-head attention with (partial, half-split) rotary embeddings
and optional q/k/v biases, and a SwiGLU FFN.
The stated departure from the published models (RMSNorm for stablelm's
LayerNorm) is the program's, listed in the configuration file.

``precision="fp8"`` is the control: every matmul takes float8 (e4m3)
operands, scaled per tensor, and accumulates in float32.  It is the step
below the bfloat16 the configuration states.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x: jnp.ndarray) -> jnp.ndarray:
    """x rounded to float8 e4m3 under one scale for the tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(a, b, fp8: bool, eq: str = "...i,ij->...j"):
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, pct, theta):
    """x (L, H, hd): rotate the first ``pct`` of each head, halves split."""
    hd = x.shape[-1]
    rot = int(hd * pct)
    rot -= rot % 2
    if rot == 0:
        return x
    half = rot // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv  # (L, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], -1)


def _attention(p, x, cfg, fp8):
    n, h, k, hd = x.shape[0], cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, kk, v = (_mm(x, p[w], fp8) for w in ("wq", "wk", "wv"))
    if cfg.qkv_bias:
        q, kk, v = q + p["bq"], kk + p["bk"], v + p["bv"]
    q, kk, v = q.reshape(n, h, hd), kk.reshape(n, k, hd), v.reshape(n, k, hd)
    if cfg.qk_norm:
        q = _rms(q, p["q_norm"], cfg.norm_eps)
        kk = _rms(kk, p["k_norm"], cfg.norm_eps)
    pos = jnp.arange(n)
    q = _rope(q, pos, cfg.rotary_pct, cfg.rope_theta)
    kk = _rope(kk, pos, cfg.rotary_pct, cfg.rope_theta)
    rep = h // k
    kk, v = jnp.repeat(kk, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = _mm(q, kk, fp8, "qhd,khd->hqk") / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((n, n), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = _mm(a, v, fp8, "hqk,khd->qhd").reshape(n, h * hd)
    return _mm(o, p["wo"], fp8)


def _swiglu(x, wg, wu, wd, fp8):
    return _mm(jax.nn.silu(_mm(x, wg, fp8)) * _mm(x, wu, fp8), wd, fp8)


@functools.partial(jax.jit, static_argnames=("cfg", "fp8"))
def _layer(p, x, i, *, cfg, fp8):
    """Layer ``i`` of the stacked weights ``p`` (a leading layer axis)."""
    p = jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False), p)
    x = x + _attention(p["mixer"], _rms(x, p["ln1"], cfg.norm_eps), cfg, fp8)
    h = _rms(x, p["ln2"], cfg.norm_eps)
    f = p["ffn"]
    return x + _swiglu(h, f["w_gate"], f["w_up"], f["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("cfg", "fp8"))
def _head(w, x, *, cfg, fp8):
    h = _rms(x, w["final_norm"], cfg.norm_eps)
    head = w["embed"].T if cfg.tie_embeddings else w["lm_head"]
    return _mm(h, head, fp8)


@jax.jit
def _embed(table, tokens):
    return table[tokens]


def forward(w: Dict, tokens: jnp.ndarray, cfg, precision: str = "f32"):
    """Logits (L, V) float32 at every position of ``tokens`` (L,)."""
    fp8 = precision == "fp8"
    if precision not in ("f32", "fp8"):
        raise ValueError(f"precision {precision!r} not in ('f32', 'fp8')")
    x = _embed(w["embed"], tokens)
    for i in range(cfg.n_periods):
        x = _layer(w["periods"]["pos0"], x, i, cfg=cfg, fp8=fp8)
    return _head(w, x, cfg=cfg, fp8=fp8)
