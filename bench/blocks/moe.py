"""DeepSeekMoE blocks at one expert-parallel chip's share: the program's
parameter layout for them and the plain reference forward pass.

Layers (arXiv:2401.06066): ``first_k_dense`` dense pre-norm layers
(``dense.block``), then layers of multi-head attention and a fine-grained
MoE FFN.  The MoE FFN's router is a float32 softmax over all
``n_routed_experts`` experts; each token takes its ``top_k`` experts,
weighted by their probabilities (renormalised over the top-k only when
``moe.norm_topk``); the shared experts, one SwiGLU of
``n_shared_experts * expert_d_ff``, are added for every token.

The chip's share (``moe.held_experts``, listed in the configuration's
``reduced``): of each MoE layer's routed experts this chip holds the first
``held``, as chip 0 of the chips that split them by expert parallelism.
The router keeps its published width and top-k, a token routed to an expert
not held here gets nothing from it, and the shared experts are counted
once.  The program computes the same share, so the partial result goes on
to the next layer in both.

Departures from the paper: only the cut (fewer layers, a share of the
routed experts, stated in the configuration file) and random weights from
the seed.  Attention, norms and rotary are ``dense``'s, unchanged.

``precision="fp8"`` is the control, as in ``dense``: every matmul, the
router's included, takes float8 (e4m3) operands.
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
    e, h, f = m.n_routed_experts, m.n_held, m.expert_d_ff
    ffn = {"router": (d, e), "w_gate": (h, d, f), "w_up": (h, d, f),
           "w_down": (h, f, d)}
    if m.n_shared_experts:
        fs = m.n_shared_experts * f
        ffn.update(sh_gate=(d, fs), sh_up=(d, fs), sh_down=(fs, d))
    return {"ln1": (d,), "mixer": dense.attention_shapes(cfg), "ln2": (d,),
            "ffn": ffn}


def layout(cfg) -> Dict:
    """Shape tree of the parameters (tuples of ints at the leaves)."""
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


def routed_experts(f, h, cfg, fp8):
    """The held experts' part of the routed FFN for every row of ``h``."""
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
    return jnp.einsum("te,etd->td", gates, out, precision=lax.Precision.HIGHEST)


def moe_ffn(f, h, cfg, fp8):
    y = routed_experts(f, h, cfg, fp8)
    if cfg.moe.n_shared_experts:
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
