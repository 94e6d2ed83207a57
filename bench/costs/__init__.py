"""Operations and bytes the algorithm needs, computed from shapes.

Every count is of the work a step must do, not of what the current code
does: a decode step reads the weights once at the compute dtype
(bfloat16) and each row's own K/V at its actual length.  So a program
that does less than today's (bfloat16 weights, no masked blocks) still
reads at most 100% of a peak.
"""
from __future__ import annotations

from typing import Sequence

BF16 = 2  # bytes


def _attn_proj_params(cfg) -> int:
    d, h, k, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return d * (h * hd + 2 * k * hd) + h * hd * d


def _ffn_params(cfg) -> int:
    return 3 * cfg.d_model * cfg.d_ff


def n_attention_layers(cfg) -> int:
    return cfg.n_layers


def attention_flops(cfg, kv_lens: Sequence[int]) -> float:
    """Scores and context of one attention layer: 4 * H * hd per key."""
    return 4.0 * cfg.n_heads * cfg.head_dim * float(sum(kv_lens))


def kv_bytes(cfg, kv_lens: Sequence[int]) -> float:
    """K and V of one attention layer read at the actual lengths."""
    return 2.0 * cfg.n_kv_heads * cfg.head_dim * float(sum(kv_lens)) * BF16


def decode_flops(cfg, kv_lens: Sequence[int]) -> float:
    """One decode step over rows with K/V lengths ``kv_lens`` (new token
    included): projections, FFN, attention and the LM head."""
    n = n_attention_layers(cfg)
    per_token = n * 2.0 * (_attn_proj_params(cfg) + _ffn_params(cfg))
    per_token += 2.0 * cfg.d_model * cfg.vocab_size
    return len(kv_lens) * per_token + n * attention_flops(cfg, kv_lens)


def decode_bytes(cfg, kv_lens: Sequence[int]) -> float:
    """Least bytes of one decode step: weights at bfloat16, the rows'
    embeddings, K/V read at the actual lengths and the new K/V written."""
    b = len(kv_lens)
    d = cfg.d_model
    n_layers = n_attention_layers(cfg)
    params = n_layers * (_attn_proj_params(cfg) + 2 * d + _ffn_params(cfg))
    params += d * cfg.vocab_size + d + b * d  # head, final norm, b embeddings
    new_kv = 2.0 * cfg.n_kv_heads * cfg.head_dim * b * BF16
    return params * BF16 + n_layers * (kv_bytes(cfg, kv_lens) + new_kv)


def paged_decode_kernel(cfg, kv_lens: Sequence[int]):
    """(flops, bytes) of one paged-decode attention call over one layer:
    every row's K/V at its actual length, its query in and context out."""
    qo = 2.0 * len(kv_lens) * cfg.n_heads * cfg.head_dim * BF16
    return attention_flops(cfg, kv_lens), kv_bytes(cfg, kv_lens) + qo


def least_seconds(flops: float, nbytes: float, peaks: dict):
    """(seconds, bound) of the roofline: the larger of the two times."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
