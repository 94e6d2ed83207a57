"""A block module for a DeepSeekMoE-style configuration, used by
``test_extend.py`` to show that a configuration whose blocks are not dense
joins the benchmark as new files (it is copied to ``bench/blocks/`` of a
copy of the benchmark there).

Layers: ``first_k_dense`` dense layers (``dense.block``), then layers of
attention and an MoE FFN.  The router is float32 softmax over
``router.shape[-1]`` experts, top-k, renormalised when ``moe.norm_topk``;
the shared experts (one SwiGLU of ``n_shared_experts * expert_d_ff``) are
added.  Only the experts whose weights it is given are computed: a token
routed to an expert not held here gets nothing from it.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from bench.blocks import dense


def moe_block_shapes(cfg) -> Dict:
    d, m = cfg.d_model, cfg.moe
    e, f = m.n_routed_experts, m.expert_d_ff
    ffn = {"router": (d, e), "w_gate": (e, d, f), "w_up": (e, d, f),
           "w_down": (e, f, d)}
    if m.n_shared_experts:
        fs = m.n_shared_experts * f
        ffn.update(sh_gate=(d, fs), sh_up=(d, fs), sh_down=(fs, d))
    return {"ln1": (d,), "mixer": dense.attention_shapes(cfg), "ln2": (d,),
            "ffn": ffn}


def layout(cfg) -> Dict:
    if cfg.moe is None or cfg.mla is not None or len(cfg.period) != 1 or \
            cfg.period[0].mixer != "attn" or cfg.period[0].ffn != "moe":
        raise ValueError(f"{cfg.name}: not attention layers with an MoE FFN")
    tree = {
        "embed": (cfg.vocab_size, cfg.d_model),
        "final_norm": (cfg.d_model,),
        "head_layers": tuple(dense.block_shapes(cfg)
                             for _ in range(cfg.first_k_dense)),
        "periods": {"pos0": dense.stacked(moe_block_shapes(cfg), cfg.n_periods)},
    }
    if not cfg.tie_embeddings:
        tree["lm_head"] = (cfg.d_model, cfg.vocab_size)
    return tree


def shared_experts(f, h, cfg, fp8):
    return dense.swiglu(h, f["sh_gate"], f["sh_up"], f["sh_down"], fp8)


def moe_ffn(f, h, cfg, fp8):
    m = cfg.moe
    probs = jax.nn.softmax(dense.mm(h, f["router"], fp8), axis=-1)
    top, ids = lax.top_k(probs, m.top_k)
    if m.norm_topk:
        top = top / jnp.maximum(top.sum(-1, keepdims=True), 1e-9)
    held = f["w_gate"].shape[0]
    # (L, held) routing weight of each held expert; ids past them add none
    gates = jnp.sum(jax.nn.one_hot(ids, held) * top[..., None], axis=1)
    g = dense.mm(h, f["w_gate"], fp8, "td,edf->etf")
    u = dense.mm(h, f["w_up"], fp8, "td,edf->etf")
    out = dense.mm(jax.nn.silu(g) * u, f["w_down"], fp8, "etf,efd->etd")
    y = jnp.einsum("te,etd->td", gates, out, precision=lax.Precision.HIGHEST)
    if m.n_shared_experts:
        y = y + shared_experts(f, h, cfg, fp8)
    return y


def moe_block(p, x, cfg, fp8):
    x = x + dense.attention(p["mixer"], dense.rms(x, p["ln1"], cfg.norm_eps),
                            cfg, fp8)
    return x + moe_ffn(p["ffn"], dense.rms(x, p["ln2"], cfg.norm_eps), cfg, fp8)


@functools.partial(jax.jit, static_argnames=("cfg", "fp8"))
def _head_layer(p, x, *, cfg, fp8):
    return dense.block(p, x, cfg, fp8)


@functools.partial(jax.jit, static_argnames=("cfg", "fp8"))
def _moe_layer(p, x, i, *, cfg, fp8):
    return moe_block(dense.layer_of(p, i), x, cfg, fp8)


def forward(w: Dict, tokens: jnp.ndarray, cfg, precision: str = "f32"):
    """Logits (L, V) float32 at every position of ``tokens`` (L,)."""
    fp8 = dense.is_fp8(precision)
    x = dense.embed(w["embed"], tokens)
    for p in w["head_layers"]:
        x = _head_layer(p, x, cfg=cfg, fp8=fp8)
    for i in range(cfg.n_periods):
        x = _moe_layer(w["periods"]["pos0"], x, i, cfg=cfg, fp8=fp8)
    return dense.head(w, x, cfg=cfg, fp8=fp8)
