"""GQA attention (covers dense / hybrid / vlm / audio archs).

Supports: grouped KV heads, qk-norm (Qwen3), QKV bias (Qwen1.5), partial
rotary (StableLM-2), explicit head_dim != d_model / n_heads (Qwen3-32B),
prefill -> KV cache, per-sequence decode positions.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels.flash_attention.ops import decode_attention, flash_attention
from repro.kernels.flash_decode.ops import (
    paged_decode_attention,
    paged_prefill_attention,
)
from repro.models.layers import apply_rope, cast_to, rms_norm
from repro.models.param import ann


def init_attention(key: jax.Array, cfg: ArchConfig) -> Dict:
    """Projections are stored FLATTENED — (d, H*hd) etc. — so tensor
    parallelism shards the H*hd product even when H itself doesn't divide
    the model axis (qwen3-14b: 40 heads, musicgen: 24 heads, GQA kv=8 on a
    16-way axis)."""
    d, h, k_, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    so = 1.0 / math.sqrt(h * hd)
    p = {
        "wq": ann(jax.random.normal(keys[0], (d, h * hd), jnp.float32) * s,
                  "embed", "heads_flat"),
        "wk": ann(jax.random.normal(keys[1], (d, k_ * hd), jnp.float32) * s,
                  "embed", "kv_flat"),
        "wv": ann(jax.random.normal(keys[2], (d, k_ * hd), jnp.float32) * s,
                  "embed", "kv_flat"),
        "wo": ann(jax.random.normal(keys[3], (h * hd, d), jnp.float32) * so,
                  "heads_flat", "embed"),
    }
    if cfg.qkv_bias:
        p["bq"] = ann(jnp.zeros((h * hd,), jnp.float32), "heads_flat")
        p["bk"] = ann(jnp.zeros((k_ * hd,), jnp.float32), "kv_flat")
        p["bv"] = ann(jnp.zeros((k_ * hd,), jnp.float32), "kv_flat")
    if cfg.qk_norm:
        p["q_norm"] = ann(jnp.ones((hd,), jnp.float32), "norm")
        p["k_norm"] = ann(jnp.ones((hd,), jnp.float32), "norm")
    return p


def init_attention_cache(cfg: ArchConfig, batch: int, max_seq: int) -> Dict:
    hd = cfg.head_dim
    shape = (batch, cfg.n_kv_heads, max_seq, hd)
    return {
        "k": jnp.zeros(shape, jnp.dtype(cfg.dtype)),
        "v": jnp.zeros(shape, jnp.dtype(cfg.dtype)),
    }


CACHE_AXES = {
    # cache_head_dim claims the model axis when kv_heads doesn't divide it
    "k": ("cache_batch", "act_kv_heads", "cache_seq", "cache_head_dim"),
    "v": ("cache_batch", "act_kv_heads", "cache_seq", "cache_head_dim"),
}


def _project_qkv(p: Dict, x: jnp.ndarray, cfg: ArchConfig, positions: jnp.ndarray,
                 constrain_fn=None):
    dt = cfg.dtype
    b, s, _ = x.shape
    h, k_, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    xc = cast_to(x, dt)
    q = xc @ cast_to(p["wq"], dt)
    k = xc @ cast_to(p["wk"], dt)
    v = xc @ cast_to(p["wv"], dt)
    if cfg.qkv_bias:
        q = q + cast_to(p["bq"], dt)[None, None]
        k = k + cast_to(p["bk"], dt)[None, None]
        v = v + cast_to(p["bv"], dt)[None, None]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, k_, hd)
    v = v.reshape(b, s, k_, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, rotary_pct=cfg.rotary_pct, theta=cfg.rope_theta)
    k = apply_rope(k, positions, rotary_pct=cfg.rotary_pct, theta=cfg.rope_theta)
    if constrain_fn is not None:
        q = constrain_fn(q, ("batch", "seq", "act_heads", None))
        k = constrain_fn(k, ("batch", "seq", "act_kv_heads", None))
        v = constrain_fn(v, ("batch", "seq", "act_kv_heads", None))
    return q, k, v


def apply_attention(
    p: Dict,
    x: jnp.ndarray,  # (B, S, d)
    cfg: ArchConfig,
    *,
    mode: str,  # "train" | "prefill"
    kv_lens: Optional[jnp.ndarray] = None,  # (B,) valid lengths
    constrain_fn=None,
    block_q: int = 512,
    block_k: int = 512,
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    b, s, _ = x.shape
    positions = jnp.arange(s, dtype=jnp.int32)[None].repeat(b, 0)
    q, k, v = _project_qkv(p, x, cfg, positions, constrain_fn)
    qt = q.transpose(0, 2, 1, 3)  # (B, H, S, hd)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention(
        qt, kt, vt, causal=True,
        kv_lens=None if kv_lens is None else kv_lens.astype(jnp.float32),
        block_q=block_q, block_k=block_k)
    out = out.transpose(0, 2, 1, 3)  # (B, S, H, hd)
    y = out.reshape(b, s, cfg.n_heads * cfg.head_dim) @ cast_to(
        p["wo"], cfg.dtype)
    cache = None
    if mode == "prefill":
        cache = {"k": kt, "v": vt}
    return y, cache


def apply_attention_decode_paged(
    p: Dict,
    x: jnp.ndarray,  # (B, 1, d) one new token
    cfg: ArchConfig,
    cache: Dict,  # k/v pages: (n_pages, Hk, page_size, hd)
    lengths: jnp.ndarray,  # (B,) current fill (also = new token position)
    page_tables: jnp.ndarray,  # (B, pages_per_seq) physical page ids
    *,
    page_size: int,
    paged_impl: str = "stream",
    pages_per_program: Optional[int] = None,
) -> Tuple[jnp.ndarray, Dict]:
    """Paged-KV decode: scatter the new token's K/V into its page, then run
    decode attention against the page pool in place.  ``paged_impl`` picks
    the implementation (paged-native stream/pallas, or the legacy dense
    gather oracle — all bit-identical, see kernels/flash_decode/ops.py)."""
    b = x.shape[0]
    positions = lengths[:, None].astype(jnp.int32)
    q, k, v = _project_qkv(p, x, cfg, positions, None)
    # new-token K/V: (B, 1, Hk, hd) -> (B, Hk, hd)
    k_new = k[:, 0]
    v_new = v[:, 0]
    page_idx = lengths // page_size
    offset = lengths % page_size
    pid = jnp.take_along_axis(page_tables, page_idx[:, None], axis=1)[:, 0]
    k_pages = cache["k"].at[pid, :, offset, :].set(
        k_new.astype(cache["k"].dtype))
    v_pages = cache["v"].at[pid, :, offset, :].set(
        v_new.astype(cache["v"].dtype))
    out = paged_decode_attention(
        q[:, 0], k_pages, v_pages, lengths + 1, page_tables,
        impl=paged_impl, pages_per_program=pages_per_program)  # (B, H, hd)
    y = out.reshape(b, cfg.n_heads * cfg.head_dim) @ cast_to(
        p["wo"], cfg.dtype)
    return y[:, None, :], {"k": k_pages, "v": v_pages}


def apply_attention_prefill_paged(
    p: Dict,
    x: jnp.ndarray,  # (1, C, d) one prompt chunk, padded to C tokens
    cfg: ArchConfig,
    cache: Dict,  # k/v pages: (n_pages, Hk, page_size, hd)
    n_valid: jnp.ndarray,  # () valid tokens in this chunk (<= C)
    page_tables: jnp.ndarray,  # (1, pages_per_seq)
    *,
    s0: int,  # static absolute position of the chunk's first token
    page_size: int,
    scratch_page: int = 0,
    block_q: int = 16,
    block_k: int = 16,
) -> Tuple[jnp.ndarray, Dict]:
    """Chunked paged prefill: scatter the chunk's K/V into the request's
    pages at absolute positions ``s0 + i``, then run causally-masked flash
    over the gathered page row with a static ``q_offset`` so the key
    blocking starts from absolute position 0 — bitwise the block schedule
    of a monolithic prefill.  Padded chunk tail tokens are routed to the
    scratch page and masked by ``kv_lens``; real positions past the prompt
    are only ever read after being overwritten by a later chunk/decode."""
    c = x.shape[1]
    pos = s0 + jnp.arange(c, dtype=jnp.int32)
    positions = pos[None]  # (1, C)
    q, k, v = _project_qkv(p, x, cfg, positions, None)
    valid = jnp.arange(c) < n_valid
    page_idx = jnp.clip(pos // page_size, 0, page_tables.shape[1] - 1)
    pid = jnp.where(valid, page_tables[0, page_idx], scratch_page)
    offset = pos % page_size
    k_pages = cache["k"].at[pid, :, offset, :].set(k[0].astype(cache["k"].dtype))
    v_pages = cache["v"].at[pid, :, offset, :].set(v[0].astype(cache["v"].dtype))
    kv_lens = (s0 + n_valid)[None].astype(jnp.int32)  # (1,)
    out = paged_prefill_attention(
        q.transpose(0, 2, 1, 3), k_pages, v_pages, kv_lens, page_tables,
        q_offset=s0, block_q=block_q, block_k=block_k)  # (1, H, C, hd)
    y = out.transpose(0, 2, 1, 3).reshape(1, c, cfg.n_heads * cfg.head_dim)
    y = y @ cast_to(p["wo"], cfg.dtype)
    return y, {"k": k_pages, "v": v_pages}


def apply_attention_decode(
    p: Dict,
    x: jnp.ndarray,  # (B, 1, d) one new token
    cfg: ArchConfig,
    cache: Dict,
    lengths: jnp.ndarray,  # (B,) current cache fill (also = new token position)
    *,
    constrain_fn=None,
) -> Tuple[jnp.ndarray, Dict]:
    b = x.shape[0]
    positions = lengths[:, None].astype(jnp.int32)  # (B, 1)
    q, k, v = _project_qkv(p, x, cfg, positions, None)
    # insert new kv at per-sequence position
    k_new = k.transpose(0, 2, 1, 3)  # (B, K, 1, hd)
    v_new = v.transpose(0, 2, 1, 3)

    def upd(cache_b, new_b, len_b):
        return jax.lax.dynamic_update_slice(cache_b, new_b, (0, len_b, 0))

    k_cache = jax.vmap(upd)(cache["k"], k_new.astype(cache["k"].dtype), lengths)
    v_cache = jax.vmap(upd)(cache["v"], v_new.astype(cache["v"].dtype), lengths)
    if constrain_fn is not None:
        k_cache = constrain_fn(k_cache, CACHE_AXES["k"])
        v_cache = constrain_fn(v_cache, CACHE_AXES["v"])
    out = decode_attention(q[:, 0], k_cache, v_cache, lengths + 1)  # (B, H, hd)
    y = out.reshape(b, cfg.n_heads * cfg.head_dim) @ cast_to(p["wo"], cfg.dtype)
    return y[:, None, :], {"k": k_cache, "v": v_cache}
