"""Operations and bytes of a decode step of DeepSeekMoE-style layers
(``first_k_dense`` dense layers, then attention and an MoE FFN of which
this chip holds ``moe.n_held`` routed experts), from shapes, as
``bench/costs`` counts dense layers: the work the step needs, not what
today's code does.

A token runs its top-k of the E routed experts, so on this chip it runs
k * held / E of them on average, the shared experts and the router always.
A decode step of B rows reads a held expert's weights only if some row
routes to it: held * (1 - (1 - k/E)^B) of them on average.
"""
from __future__ import annotations

from typing import Sequence

from bench import costs
from bench.costs import BF16


def _expert_params(cfg) -> int:
    return 3 * cfg.d_model * cfg.moe.expert_d_ff


def _always_params(cfg) -> int:
    """Parameters of an MoE layer every token uses: attention, the router,
    the shared experts."""
    m = cfg.moe
    return (costs._attn_proj_params(cfg) + cfg.d_model * m.n_routed_experts
            + m.n_shared_experts * _expert_params(cfg))


def routed_per_token(cfg) -> float:
    """Held experts a token runs, on average: k * held / E."""
    m = cfg.moe
    return m.top_k * m.n_held / m.n_routed_experts


def touched_experts(cfg, rows: int) -> float:
    """Held experts that some of ``rows`` tokens route to, on average."""
    m = cfg.moe
    return m.n_held * (1.0 - (1.0 - m.top_k / m.n_routed_experts) ** rows)


def _dense_layer_params(cfg) -> int:
    return costs._attn_proj_params(cfg) + costs._ffn_params(cfg)


def decode_flops(cfg, kv_lens: Sequence[int]) -> float:
    """One decode step over rows with K/V lengths ``kv_lens`` (new token
    included): the dense layers, each MoE layer's attention, router, shared
    and routed experts, attention at the actual lengths and the LM head."""
    moe = _always_params(cfg) + routed_per_token(cfg) * _expert_params(cfg)
    per_token = 2.0 * (cfg.first_k_dense * _dense_layer_params(cfg)
                       + cfg.n_periods * moe + cfg.d_model * cfg.vocab_size)
    return len(kv_lens) * per_token + \
        cfg.n_layers * costs.attention_flops(cfg, kv_lens)


def decode_bytes(cfg, kv_lens: Sequence[int]) -> float:
    """Least bytes of one decode step: weights at bfloat16 with the held
    experts that the rows route to, the rows' embeddings, K/V read at the
    actual lengths and the new K/V written."""
    b, d = len(kv_lens), cfg.d_model
    norms = 2 * d
    moe = norms + _always_params(cfg) + \
        touched_experts(cfg, b) * _expert_params(cfg)
    params = cfg.first_k_dense * (norms + _dense_layer_params(cfg)) + \
        cfg.n_periods * moe
    params += d * cfg.vocab_size + d + b * d  # head, final norm, b embeddings
    new_kv = 2.0 * cfg.n_kv_heads * cfg.head_dim * b * BF16
    return params * BF16 + cfg.n_layers * (costs.kv_bytes(cfg, kv_lens) + new_kv)
