"""Dense pre-norm decoder blocks: the program's parameter layout for them
and the plain reference forward pass.

``layout`` writes out, from the configuration, the tree the program's
``LM`` takes (an embedding, ``periods`` stacked on a leading layer axis,
``final_norm``, ``lm_head``); it is not read from the program, and
``weights.check_layout`` refuses a program whose tree differs.

``forward`` is ``jax.numpy`` in float32 at ``highest`` matmul precision,
one layer at a time, independent of the program's code.  It computes what
the configuration states: pre-norm decoder layers of RMSNorm, multi-head
attention with (partial, half-split) rotary embeddings and optional q/k/v
biases, and a SwiGLU FFN.  The stated departure from the published models
(RMSNorm for stablelm's LayerNorm) is the program's, listed in the
configuration file.

``precision="fp8"`` is the control: every matmul takes float8 (e4m3)
operands, scaled per tensor, and accumulates in float32.  It is the step
below the bfloat16 the configuration states.

Other block families (``bench/blocks/<name>.py``) reuse the attention,
norm and matmul helpers here.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


# ---------------------------------------------------------------------------
# parameter layout
# ---------------------------------------------------------------------------
def attention_shapes(cfg) -> Dict[str, Tuple[int, ...]]:
    d, h, k, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    out = {"wq": (d, h * hd), "wk": (d, k * hd), "wv": (d, k * hd),
           "wo": (h * hd, d)}
    if cfg.qkv_bias:
        out.update(bq=(h * hd,), bk=(k * hd,), bv=(k * hd,))
    if cfg.qk_norm:
        out.update(q_norm=(hd,), k_norm=(hd,))
    return out


def block_shapes(cfg) -> Dict:
    d = cfg.d_model
    ffn = {"w_gate": (d, cfg.d_ff), "w_up": (d, cfg.d_ff),
           "w_down": (cfg.d_ff, d)}
    return {"ln1": (d,), "mixer": attention_shapes(cfg), "ln2": (d,), "ffn": ffn}


def stacked(tree, n: int):
    """``tree``'s shapes with a leading axis of ``n`` layers."""
    return jax.tree.map(lambda s: (n,) + s, tree,
                        is_leaf=lambda x: isinstance(x, tuple))


def layout(cfg) -> Dict:
    """Shape tree of the parameters (tuples of ints at the leaves)."""
    if any(s.mixer != "attn" or s.ffn != "dense" for s in cfg.period) \
            or len(cfg.period) != 1 or cfg.mla is not None or cfg.first_k_dense:
        raise ValueError(f"{cfg.name}: the benchmark serves attention "
                         "layers with a dense FFN, one per period")
    tree = {
        "embed": (cfg.vocab_size, cfg.d_model),
        "final_norm": (cfg.d_model,),
        "periods": {"pos0": stacked(block_shapes(cfg), cfg.n_periods)},
    }
    if not cfg.tie_embeddings:
        tree["lm_head"] = (cfg.d_model, cfg.vocab_size)
    return tree


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------
def q8(x: jnp.ndarray) -> jnp.ndarray:
    """x rounded to float8 e4m3 under one scale for the tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def mm(a, b, fp8: bool, eq: str = "...i,ij->...j"):
    if fp8:
        a, b = q8(a), q8(b)
    return jnp.einsum(eq, a, b, precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, pct, theta):
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


def attention(p, x, cfg, fp8):
    n, h, k, hd = x.shape[0], cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, kk, v = (mm(x, p[w], fp8) for w in ("wq", "wk", "wv"))
    if cfg.qkv_bias:
        q, kk, v = q + p["bq"], kk + p["bk"], v + p["bv"]
    q, kk, v = q.reshape(n, h, hd), kk.reshape(n, k, hd), v.reshape(n, k, hd)
    if cfg.qk_norm:
        q = rms(q, p["q_norm"], cfg.norm_eps)
        kk = rms(kk, p["k_norm"], cfg.norm_eps)
    pos = jnp.arange(n)
    q = rope(q, pos, cfg.rotary_pct, cfg.rope_theta)
    kk = rope(kk, pos, cfg.rotary_pct, cfg.rope_theta)
    rep = h // k
    kk, v = jnp.repeat(kk, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = mm(q, kk, fp8, "qhd,khd->hqk") / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((n, n), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = mm(a, v, fp8, "hqk,khd->qhd").reshape(n, h * hd)
    return mm(o, p["wo"], fp8)


def swiglu(x, wg, wu, wd, fp8):
    return mm(jax.nn.silu(mm(x, wg, fp8)) * mm(x, wu, fp8), wd, fp8)


def block(p, x, cfg, fp8):
    """One pre-norm layer with a dense SwiGLU FFN (unstacked weights)."""
    x = x + attention(p["mixer"], rms(x, p["ln1"], cfg.norm_eps), cfg, fp8)
    h = rms(x, p["ln2"], cfg.norm_eps)
    f = p["ffn"]
    return x + swiglu(h, f["w_gate"], f["w_up"], f["w_down"], fp8)


def layer_of(p, i):
    """Layer ``i`` of weights stacked on a leading layer axis."""
    return jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False), p)


@functools.partial(jax.jit, static_argnames=("cfg", "fp8"))
def _layer(p, x, i, *, cfg, fp8):
    """Layer ``i`` of the stacked weights ``p`` (a leading layer axis)."""
    return block(layer_of(p, i), x, cfg, fp8)


@functools.partial(jax.jit, static_argnames=("cfg", "fp8"))
def head(w, x, *, cfg, fp8):
    h = rms(x, w["final_norm"], cfg.norm_eps)
    table = w["embed"].T if cfg.tie_embeddings else w["lm_head"]
    return mm(h, table, fp8)


@jax.jit
def embed(table, tokens):
    return table[tokens]


def is_fp8(precision: str) -> bool:
    """Whether ``precision`` asks for the fp8 control."""
    if precision not in ("f32", "fp8"):
        raise ValueError(f"precision {precision!r} not in ('f32', 'fp8')")
    return precision == "fp8"


def forward(w: Dict, tokens: jnp.ndarray, cfg, precision: str = "f32"):
    """Logits (L, V) float32 at every position of ``tokens`` (L,)."""
    fp8 = is_fp8(precision)
    x = embed(w["embed"], tokens)
    for i in range(cfg.n_periods):
        x = _layer(w["periods"]["pos0"], x, i, cfg=cfg, fp8=fp8)
    return head(w, x, cfg=cfg, fp8=fp8)
