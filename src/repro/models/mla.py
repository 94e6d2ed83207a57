"""Multi-head Latent Attention (DeepSeek-V2).

Train/prefill: queries via a low-rank bottleneck (q_lora), KV via a shared
compressed latent c_kv (kv_lora=512) plus a single shared rotary key slice;
attention runs as MHA with qk dim = nope+rope and separate v dim.

Decode caches ONLY (c_kv, k_pe) — the MLA memory win.  Two decode paths:

* ``absorb=False`` (naive): re-expands K/V from the latent cache blockwise
  (flash-decode style online softmax over chunks), paying
  O(S * kv_lora * H * (nope+v)) FLOPs per token.
* ``absorb=True``: absorbs W_uk into the query and W_uv into the output so
  attention runs directly in the latent space — scores against c_kv, context
  in latent space, one (H, kv_lora, v) expansion at the end.  This is the
  DeepSeek-paper inference optimization; EXPERIMENTS.md §Perf quantifies it.

The *paged* decode path (``apply_mla_decode_paged``, serving) is
paged-native and always absorbed: scores and context read the latent page
pool in place via ``kernels.flash_decode.ops.paged_latent_decode_attention``
(stream / pallas / gather impls, mutually bit-exact for stream/gather);
``paged_impl="legacy"`` keeps the old gather + ``_mla_decode_attn`` path.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_decode.ops import (
    gather_pages,
    paged_latent_decode_attention,
)
from repro.models.layers import apply_rope, cast_to, rms_norm
from repro.models.param import ann

NEG_INF = -1e30


def init_mla(key: jax.Array, cfg: ArchConfig) -> Dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    keys = jax.random.split(key, 5)
    # up-projections stored flattened (lora, H*dim) so TP shards H*dim even
    # when H doesn't divide the model axis
    return {
        "wq_a": ann(jax.random.normal(keys[0], (d, m.q_lora_rank), jnp.float32)
                    / math.sqrt(d), "embed", "lora"),
        "q_a_norm": ann(jnp.ones((m.q_lora_rank,), jnp.float32), "norm"),
        "wq_b": ann(jax.random.normal(keys[1], (m.q_lora_rank, h * qk_dim),
                                      jnp.float32)
                    / math.sqrt(m.q_lora_rank), "lora", "heads_flat"),
        "wkv_a": ann(jax.random.normal(
            keys[2], (d, m.kv_lora_rank + m.qk_rope_head_dim), jnp.float32)
            / math.sqrt(d), "embed", "lora"),
        "kv_a_norm": ann(jnp.ones((m.kv_lora_rank,), jnp.float32), "norm"),
        "wkv_b": ann(jax.random.normal(
            keys[3], (m.kv_lora_rank, h * (m.qk_nope_head_dim + m.v_head_dim)),
            jnp.float32) / math.sqrt(m.kv_lora_rank),
            "lora", "heads_flat"),
        "wo": ann(jax.random.normal(keys[4], (h * m.v_head_dim, d), jnp.float32)
                  / math.sqrt(h * m.v_head_dim), "heads_flat", "embed"),
    }


def init_mla_cache(cfg: ArchConfig, batch: int, max_seq: int) -> Dict:
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, max_seq, m.kv_lora_rank), jnp.dtype(cfg.dtype)),
        "kpe": jnp.zeros((batch, max_seq, m.qk_rope_head_dim), jnp.dtype(cfg.dtype)),
    }


MLA_CACHE_AXES = {
    "ckv": ("cache_batch", "cache_seq", "cache_latent"),
    "kpe": ("cache_batch", "cache_seq", None),
}


def _mla_q(p: Dict, x: jnp.ndarray, cfg: ArchConfig, positions: jnp.ndarray):
    m, dt = cfg.mla, cfg.dtype
    b, s, _ = x.shape
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    xc = cast_to(x, dt)
    cq = rms_norm(xc @ cast_to(p["wq_a"], dt), p["q_a_norm"], cfg.norm_eps)
    q = (cq @ cast_to(p["wq_b"], dt)).reshape(b, s, cfg.n_heads, qk_dim)
    q_nope = q[..., : m.qk_nope_head_dim]
    q_pe = apply_rope(q[..., m.qk_nope_head_dim:], positions, theta=cfg.rope_theta)
    return q_nope, q_pe


def _mla_kv_latent(p: Dict, x: jnp.ndarray, cfg: ArchConfig, positions: jnp.ndarray):
    m, dt = cfg.mla, cfg.dtype
    xc = cast_to(x, dt)
    kv_a = xc @ cast_to(p["wkv_a"], dt)
    ckv = rms_norm(kv_a[..., : m.kv_lora_rank], p["kv_a_norm"], cfg.norm_eps)
    kpe = apply_rope(kv_a[..., m.kv_lora_rank:][:, :, None, :], positions,
                     theta=cfg.rope_theta)[:, :, 0, :]  # (B,S,rope)
    return ckv, kpe


def apply_mla(
    p: Dict,
    x: jnp.ndarray,
    cfg: ArchConfig,
    *,
    mode: str,  # "train" | "prefill"
    kv_lens: Optional[jnp.ndarray] = None,
    constrain_fn=None,
    block_q: int = 512,
    block_k: int = 512,
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    m, dt = cfg.mla, cfg.dtype
    b, s, _ = x.shape
    positions = jnp.arange(s, dtype=jnp.int32)[None].repeat(b, 0)
    q_nope, q_pe = _mla_q(p, x, cfg, positions)
    ckv, kpe = _mla_kv_latent(p, x, cfg, positions)
    kv = (ckv @ cast_to(p["wkv_b"], dt)).reshape(
        b, s, cfg.n_heads, m.qk_nope_head_dim + m.v_head_dim)
    k_nope = kv[..., : m.qk_nope_head_dim]
    v = kv[..., m.qk_nope_head_dim:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kpe[:, :, None, :],
                                  (*k_nope.shape[:3], m.qk_rope_head_dim))],
        axis=-1)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    if constrain_fn is not None:
        q = constrain_fn(q, ("batch", "seq", "act_heads", None))
        k = constrain_fn(k, ("batch", "seq", "act_heads", None))
        v = constrain_fn(v, ("batch", "seq", "act_heads", None))
    out = flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=True, sm_scale=1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim),
        kv_lens=None if kv_lens is None else kv_lens.astype(jnp.float32),
        block_q=block_q, block_k=block_k)
    out = out.transpose(0, 2, 1, 3)  # (B,S,H,v)
    y = out.reshape(b, s, cfg.n_heads * m.v_head_dim) @ cast_to(p["wo"], dt)
    cache = {"ckv": ckv, "kpe": kpe} if mode == "prefill" else None
    return y, cache


def apply_mla_decode(
    p: Dict,
    x: jnp.ndarray,  # (B, 1, d)
    cfg: ArchConfig,
    cache: Dict,
    lengths: jnp.ndarray,  # (B,)
    *,
    absorb: bool = False,
    chunk: int = 2048,
    constrain_fn=None,
) -> Tuple[jnp.ndarray, Dict]:
    m, dt = cfg.mla, cfg.dtype
    b = x.shape[0]
    h = cfg.n_heads
    positions = lengths[:, None].astype(jnp.int32)
    q_nope, q_pe = _mla_q(p, x, cfg, positions)       # (B,1,H,·)
    ckv_new, kpe_new = _mla_kv_latent(p, x, cfg, positions)

    def upd(cache_b, new_b, len_b):
        return lax.dynamic_update_slice(cache_b, new_b, (len_b, 0))

    ckv_c = jax.vmap(upd)(cache["ckv"], ckv_new.astype(cache["ckv"].dtype), lengths)
    kpe_c = jax.vmap(upd)(cache["kpe"], kpe_new.astype(cache["kpe"].dtype), lengths)
    if constrain_fn is not None:
        ckv_c = constrain_fn(ckv_c, MLA_CACHE_AXES["ckv"])
        kpe_c = constrain_fn(kpe_c, MLA_CACHE_AXES["kpe"])
    new_cache = {"ckv": ckv_c, "kpe": kpe_c}
    out = _mla_decode_attn(p, q_nope[:, 0], q_pe[:, 0], ckv_c, kpe_c,
                           lengths + 1, cfg, absorb=absorb, chunk=chunk)
    y = out.reshape(b, h * m.v_head_dim) @ cast_to(p["wo"], dt)
    return y[:, None, :], new_cache


def apply_mla_decode_paged(
    p: Dict,
    x: jnp.ndarray,  # (B, 1, d)
    cfg: ArchConfig,
    cache: Dict,  # ckv pages (n_pages, page_size, r); kpe (n_pages, page_size, rope)
    lengths: jnp.ndarray,  # (B,)
    page_tables: jnp.ndarray,  # (B, pages_per_seq)
    *,
    page_size: int,
    absorb: bool = False,
    chunk: int = 2048,
    paged_impl: str = "stream",
    pages_per_program: Optional[int] = None,
) -> Tuple[jnp.ndarray, Dict]:
    """Paged latent-cache decode: scatter the new (c_kv, k_pe) into its page,
    then attend over the latent pool in place (absorbed form: W_uk folded
    into the query, W_uv applied once to the latent context), via
    ``paged_latent_decode_attention``.  ``paged_impl="legacy"`` keeps the
    pre-paged-native behavior: gather contiguous views and run
    ``_mla_decode_attn`` with the caller's ``absorb``/``chunk``."""
    m, dt = cfg.mla, cfg.dtype
    b, h = x.shape[0], cfg.n_heads
    positions = lengths[:, None].astype(jnp.int32)
    q_nope, q_pe = _mla_q(p, x, cfg, positions)
    ckv_new, kpe_new = _mla_kv_latent(p, x, cfg, positions)
    page_idx = lengths // page_size
    offset = lengths % page_size
    pid = jnp.take_along_axis(page_tables, page_idx[:, None], axis=1)[:, 0]
    ckv_pages = cache["ckv"].at[pid, offset, :].set(
        ckv_new[:, 0].astype(cache["ckv"].dtype))
    kpe_pages = cache["kpe"].at[pid, offset, :].set(
        kpe_new[:, 0].astype(cache["kpe"].dtype))
    new_cache = {"ckv": ckv_pages, "kpe": kpe_pages}
    if paged_impl == "legacy":
        n_pp = page_tables.shape[1]
        ckv_c = ckv_pages[page_tables].reshape(b, n_pp * page_size,
                                               m.kv_lora_rank)
        kpe_c = kpe_pages[page_tables].reshape(b, n_pp * page_size,
                                               m.qk_rope_head_dim)
        out = _mla_decode_attn(p, q_nope[:, 0], q_pe[:, 0], ckv_c, kpe_c,
                               lengths + 1, cfg, absorb=absorb, chunk=chunk)
        y = out.reshape(b, h * m.v_head_dim) @ cast_to(p["wo"], dt)
        return y[:, None, :], new_cache
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    wkv_b = cast_to(p["wkv_b"], dt).reshape(
        m.kv_lora_rank, h, m.qk_nope_head_dim + m.v_head_dim)
    wk = wkv_b[..., : m.qk_nope_head_dim]
    wv = wkv_b[..., m.qk_nope_head_dim:]
    q_lat = jnp.einsum("bhe,rhe->bhr", q_nope[:, 0], wk)  # (B, H, r)
    ctx_lat = paged_latent_decode_attention(
        q_lat, q_pe[:, 0], ckv_pages, kpe_pages, lengths + 1, page_tables,
        sm_scale=scale, impl=paged_impl,
        pages_per_program=pages_per_program)
    out = jnp.einsum("bhr,rhe->bhe", ctx_lat.astype(dt), wv)  # (B, H, v)
    y = out.reshape(b, h * m.v_head_dim) @ cast_to(p["wo"], dt)
    return y[:, None, :], new_cache


def apply_mla_prefill_paged(
    p: Dict,
    x: jnp.ndarray,  # (1, C, d) one prompt chunk, padded to C tokens
    cfg: ArchConfig,
    cache: Dict,  # latent pages: ckv (n_pages, page, r), kpe (n_pages, page, rope)
    n_valid: jnp.ndarray,  # () valid tokens in this chunk (<= C)
    page_tables: jnp.ndarray,  # (1, pages_per_seq)
    *,
    s0: int,  # static absolute position of the chunk's first token
    page_size: int,
    scratch_page: int = 0,
    block_q: int = 16,
    block_k: int = 16,
) -> Tuple[jnp.ndarray, Dict]:
    """Chunked paged MLA prefill: scatter the chunk's (c_kv, k_pe) into the
    latent pages, gather the request's full latent row, re-expand K/V with
    ``wkv_b`` (row-stable matmul, so earlier positions are bitwise those of
    a monolithic prefill), and run causal flash with static ``q_offset``.
    Padded chunk tail tokens are routed to the scratch page."""
    m, dt = cfg.mla, cfg.dtype
    c, h = x.shape[1], cfg.n_heads
    pos = s0 + jnp.arange(c, dtype=jnp.int32)
    positions = pos[None]  # (1, C)
    q_nope, q_pe = _mla_q(p, x, cfg, positions)          # (1,C,H,·)
    ckv_new, kpe_new = _mla_kv_latent(p, x, cfg, positions)
    valid = jnp.arange(c) < n_valid
    page_idx = jnp.clip(pos // page_size, 0, page_tables.shape[1] - 1)
    pid = jnp.where(valid, page_tables[0, page_idx], scratch_page)
    offset = pos % page_size
    ckv_pages = cache["ckv"].at[pid, offset, :].set(
        ckv_new[0].astype(cache["ckv"].dtype))
    kpe_pages = cache["kpe"].at[pid, offset, :].set(
        kpe_new[0].astype(cache["kpe"].dtype))
    new_cache = {"ckv": ckv_pages, "kpe": kpe_pages}
    ckv_full = gather_pages(ckv_pages, page_tables)  # (1, S, r)
    kpe_full = gather_pages(kpe_pages, page_tables)  # (1, S, rope)
    kv = (ckv_full @ cast_to(p["wkv_b"], dt)).reshape(
        1, ckv_full.shape[1], h, m.qk_nope_head_dim + m.v_head_dim)
    k_nope = kv[..., : m.qk_nope_head_dim]
    v = kv[..., m.qk_nope_head_dim:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kpe_full[:, :, None, :],
                                  (*k_nope.shape[:3], m.qk_rope_head_dim))],
        axis=-1)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    kv_lens = (s0 + n_valid)[None].astype(jnp.float32)  # (1,)
    out = flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True,
        sm_scale=1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim),
        kv_lens=kv_lens, q_offset=s0, block_q=block_q, block_k=block_k)
    out = out.transpose(0, 2, 1, 3)  # (1,C,H,v)
    y = out.reshape(1, c, h * m.v_head_dim) @ cast_to(p["wo"], dt)
    return y, new_cache


def _mla_decode_attn(
    p: Dict,
    q_nope1: jnp.ndarray,  # (B, H, nope)
    q_pe1: jnp.ndarray,    # (B, H, rope)
    ckv_c: jnp.ndarray,    # (B, S, r) latent cache incl. the new token
    kpe_c: jnp.ndarray,    # (B, S, rope)
    lens1: jnp.ndarray,    # (B,) valid lengths incl. the new token
    cfg: ArchConfig,
    *,
    absorb: bool,
    chunk: int,
) -> jnp.ndarray:
    """Shared decode attention over a contiguous latent cache view; returns
    (B, H, v_head_dim)."""
    m, dt = cfg.mla, cfg.dtype
    b, h = q_nope1.shape[0], cfg.n_heads
    s_max = ckv_c.shape[1]
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    wkv_b = cast_to(p["wkv_b"], dt).reshape(
        m.kv_lora_rank, h, m.qk_nope_head_dim + m.v_head_dim)
    wk = wkv_b[..., : m.qk_nope_head_dim]   # (r,H,nope)
    wv = wkv_b[..., m.qk_nope_head_dim:]    # (r,H,v)

    if absorb:
        # latent-space attention: scores vs compressed cache directly.
        # bf16 inputs with fp32 MXU accumulation — casting the whole cache
        # to fp32 would materialize 2x the cache per layer per step.
        q_lat = jnp.einsum("bhe,rhe->bhr", q_nope1, wk)  # (B,H,r)
        scores = (jnp.einsum("bhr,bsr->bhs", q_lat, ckv_c,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bhe,bse->bhs", q_pe1, kpe_c,
                               preferred_element_type=jnp.float32)) * scale
        mask = jnp.arange(s_max)[None, :] < lens1[:, None]
        scores = jnp.where(mask[:, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx_lat = jnp.einsum("bhs,bsr->bhr", probs.astype(dt), ckv_c,
                             preferred_element_type=jnp.float32)  # (B,H,r)
        out = jnp.einsum("bhr,rhe->bhe", ctx_lat.astype(dt), wv)  # (B,H,v)
    else:
        # naive: blockwise re-expansion of K/V from the latent cache with an
        # online softmax (bounded memory, heavy FLOPs)
        nchunks = max(1, -(-s_max // chunk))
        pad = nchunks * chunk - s_max
        ckv_p = jnp.pad(ckv_c, ((0, 0), (0, pad), (0, 0)))
        kpe_p = jnp.pad(kpe_c, ((0, 0), (0, pad), (0, 0)))

        def chunk_step(carry, j):
            acc, mx, l = carry
            ckv_j = lax.dynamic_slice(ckv_p, (0, j * chunk, 0), (b, chunk, m.kv_lora_rank))
            kpe_j = lax.dynamic_slice(kpe_p, (0, j * chunk, 0), (b, chunk, m.qk_rope_head_dim))
            kv_j = jnp.einsum("bsr,rhe->bshe", ckv_j, wkv_b)
            k_nope_j = kv_j[..., : m.qk_nope_head_dim]
            v_j = kv_j[..., m.qk_nope_head_dim:]
            s_j = (jnp.einsum("bhe,bshe->bhs", q_nope1.astype(jnp.float32),
                              k_nope_j.astype(jnp.float32))
                   + jnp.einsum("bhe,bse->bhs", q_pe1.astype(jnp.float32),
                                kpe_j.astype(jnp.float32))) * scale
            pos = j * chunk + jnp.arange(chunk)
            valid = pos[None, :] < lens1[:, None]
            s_j = jnp.where(valid[:, None, :], s_j, NEG_INF)
            mx_new = jnp.maximum(mx, s_j.max(-1))
            alpha = jnp.exp(mx - mx_new)
            pj = jnp.exp(s_j - mx_new[..., None])
            pj = jnp.where(valid[:, None, :], pj, 0.0)
            l_new = l * alpha + pj.sum(-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhs,bshe->bhe", pj, v_j.astype(jnp.float32))
            return (acc_new, mx_new, l_new), None

        init = (jnp.zeros((b, h, m.v_head_dim), jnp.float32),
                jnp.full((b, h), NEG_INF, jnp.float32),
                jnp.zeros((b, h), jnp.float32))
        (acc, _, l), _ = lax.scan(chunk_step, init, jnp.arange(nchunks))
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(dt)

    return out
