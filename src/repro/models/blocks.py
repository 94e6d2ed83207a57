"""Decoder blocks: (attn|mamba) mixer + (dense|moe|none) FFN, pre-norm."""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, LayerSpec
from repro.models import attention as attn_mod
from repro.models import mamba as mamba_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models.layers import apply_mlp, init_mlp, init_rmsnorm, rms_norm
from repro.models.runtime import Runtime


def _uses_mla(cfg: ArchConfig) -> bool:
    return cfg.mla is not None


def init_block(key: jax.Array, cfg: ArchConfig, spec: LayerSpec) -> Dict:
    k1, k2 = jax.random.split(key)
    p: Dict = {"ln1": init_rmsnorm(cfg.d_model)}
    if spec.mixer == "attn":
        p["mixer"] = (mla_mod.init_mla(k1, cfg) if _uses_mla(cfg)
                      else attn_mod.init_attention(k1, cfg))
    else:
        p["mixer"] = mamba_mod.init_mamba(k1, cfg)
    if spec.ffn != "none":
        p["ln2"] = init_rmsnorm(cfg.d_model)
        if spec.ffn == "dense":
            p["ffn"] = init_mlp(k2, cfg.d_model, cfg.d_ff)
        else:
            p["ffn"] = moe_mod.init_moe(k2, cfg)
    return p


def init_block_cache(cfg: ArchConfig, spec: LayerSpec, batch: int,
                     max_seq: int) -> Dict:
    if spec.mixer == "attn":
        if _uses_mla(cfg):
            return mla_mod.init_mla_cache(cfg, batch, max_seq)
        return attn_mod.init_attention_cache(cfg, batch, max_seq)
    return mamba_mod.init_mamba_cache(cfg, batch)


def block_cache_axes(cfg: ArchConfig, spec: LayerSpec) -> Dict:
    if spec.mixer == "attn":
        if _uses_mla(cfg):
            return dict(mla_mod.MLA_CACHE_AXES)
        return dict(attn_mod.CACHE_AXES)
    return dict(mamba_mod.MAMBA_CACHE_AXES)


def _apply_ffn(p: Dict, x: jnp.ndarray, cfg: ArchConfig, spec: LayerSpec,
               rt: Runtime, *, train: bool):
    """x + the layer's FFN of x; returns (x, aux_loss, counts), counts the
    MoE dispatch's int32 [routed_rows, expert_rows] (None without MoE)."""
    aux = jnp.zeros((), jnp.float32)
    if spec.ffn == "none":
        return x, aux, None
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    if spec.ffn == "dense":
        return x + apply_mlp(p["ffn"], h2, cfg.dtype, rt.constrain_fn), aux, None
    y2, aux, counts = moe_mod.apply_moe(
        p["ffn"], h2, cfg, train=train, mesh=rt.mesh, rules=rt.rules)
    return x + y2, aux, counts


def apply_block(
    p: Dict,
    x: jnp.ndarray,
    cfg: ArchConfig,
    spec: LayerSpec,
    rt: Runtime,
    *,
    mode: str,  # "train" | "prefill"
    kv_lens: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Optional[Dict], jnp.ndarray]:
    """Returns (x, cache-or-None, aux_loss)."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if spec.mixer == "attn":
        if _uses_mla(cfg):
            y, cache = mla_mod.apply_mla(
                p["mixer"], h, cfg, mode=mode, kv_lens=kv_lens,
                constrain_fn=rt.constrain_fn, block_q=rt.block_q,
                block_k=rt.block_k)
        else:
            y, cache = attn_mod.apply_attention(
                p["mixer"], h, cfg, mode=mode, kv_lens=kv_lens,
                constrain_fn=rt.constrain_fn, block_q=rt.block_q,
                block_k=rt.block_k)
    else:
        y, cache = mamba_mod.apply_mamba(
            p["mixer"], h, cfg, mode=mode, constrain_fn=rt.constrain_fn,
            scan_chunk=rt.scan_chunk)
    x = x + y
    x = rt.constrain(x, ("batch", "seq", "act_embed")) if rt.rules else x
    x, aux, _ = _apply_ffn(p, x, cfg, spec, rt, train=(mode == "train"))
    return x, cache, aux


def apply_block_decode_paged(
    p: Dict,
    x: jnp.ndarray,  # (B, 1, d)
    cfg: ArchConfig,
    spec: LayerSpec,
    rt: Runtime,
    cache: Dict,
    lengths: jnp.ndarray,
    page_tables: jnp.ndarray,  # (B, pages_per_seq) physical page ids
) -> Tuple[jnp.ndarray, Dict, Optional[jnp.ndarray]]:
    """Decode step against a paged cache: attention/MLA leaves are page-major
    ((n_pages, ..., page_size, ...)); mamba state leaves are slot-major and
    use the regular decode path unchanged.  Returns (x, cache, MoE counts
    or None)."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if spec.mixer == "attn":
        if _uses_mla(cfg):
            y, new_cache = mla_mod.apply_mla_decode_paged(
                p["mixer"], h, cfg, cache, lengths, page_tables,
                page_size=rt.page_size, absorb=rt.mla_absorb,
                paged_impl=rt.paged_impl,
                pages_per_program=rt.pages_per_program)
        else:
            y, new_cache = attn_mod.apply_attention_decode_paged(
                p["mixer"], h, cfg, cache, lengths, page_tables,
                page_size=rt.page_size, paged_impl=rt.paged_impl,
                pages_per_program=rt.pages_per_program)
    else:
        y, new_cache = mamba_mod.apply_mamba_decode(
            p["mixer"], h, cfg, cache, constrain_fn=rt.constrain_fn)
    x, _, counts = _apply_ffn(p, x + y, cfg, spec, rt, train=False)
    return x, new_cache, counts


def apply_block_prefill_paged(
    p: Dict,
    x: jnp.ndarray,  # (1, C, d) one prompt chunk
    cfg: ArchConfig,
    spec: LayerSpec,
    rt: Runtime,
    cache: Dict,
    n_valid: jnp.ndarray,  # () valid tokens in this chunk
    page_tables: jnp.ndarray,  # (1, pages_per_seq)
    *,
    s0: int,  # static absolute position of the chunk's first token
) -> Tuple[jnp.ndarray, Dict, Optional[jnp.ndarray]]:
    """Chunked-prefill step against a paged cache.  Attention-only archs:
    mamba's slot-major recurrent state has no paged/positional form, so the
    engine gates chunked prefill to attn mixers (see ServeEngine).  Returns
    (x, cache, MoE counts or None)."""
    if spec.mixer != "attn":
        raise NotImplementedError(
            "chunked paged prefill supports attn mixers only")
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if _uses_mla(cfg):
        y, new_cache = mla_mod.apply_mla_prefill_paged(
            p["mixer"], h, cfg, cache, n_valid, page_tables,
            s0=s0, page_size=rt.page_size, block_q=rt.block_q,
            block_k=rt.block_k)
    else:
        y, new_cache = attn_mod.apply_attention_prefill_paged(
            p["mixer"], h, cfg, cache, n_valid, page_tables,
            s0=s0, page_size=rt.page_size, block_q=rt.block_q,
            block_k=rt.block_k)
    x, _, counts = _apply_ffn(p, x + y, cfg, spec, rt, train=False)
    return x, new_cache, counts


def apply_block_decode(
    p: Dict,
    x: jnp.ndarray,  # (B, 1, d)
    cfg: ArchConfig,
    spec: LayerSpec,
    rt: Runtime,
    cache: Dict,
    lengths: jnp.ndarray,
) -> Tuple[jnp.ndarray, Dict]:
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if spec.mixer == "attn":
        if _uses_mla(cfg):
            y, new_cache = mla_mod.apply_mla_decode(
                p["mixer"], h, cfg, cache, lengths, absorb=rt.mla_absorb,
                constrain_fn=rt.constrain_fn)
        else:
            y, new_cache = attn_mod.apply_attention_decode(
                p["mixer"], h, cfg, cache, lengths,
                constrain_fn=rt.constrain_fn)
    else:
        y, new_cache = mamba_mod.apply_mamba_decode(
            p["mixer"], h, cfg, cache, constrain_fn=rt.constrain_fn)
    x, _, _ = _apply_ffn(p, x + y, cfg, spec, rt, train=False)
    return x, new_cache
