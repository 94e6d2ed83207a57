"""Pallas TPU flash-attention forward kernel.

Grid = (batch*heads, n_q_blocks, n_kv_blocks); the KV axis is the innermost
(sequential / "arbitrary") dimension so the (block_q, head_dim) fp32
accumulator and the (block_q,) running max / sum live in VMEM scratch across
KV iterations — the canonical TPU flash schedule.  Tiles are MXU-aligned
(block sizes multiples of 128 on real hardware; tests use smaller tiles in
interpret mode).

Layout: q (BH, Sq, D), k/v (BH, Skv, D) — GQA callers broadcast KV heads in
the ops wrapper (`flash_attention_pallas`), keeping this kernel MHA-shaped.
Causally-masked blocks are predicated off with pl.when (on TPU these tiles
are skipped by the scalar unit before any VMEM traffic is issued).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_interpret

NEG_INF = -1e30

# dot_general dimension_numbers: q @ k^T (contract last axes) / p @ v
_DOT_QK = (((1,), (1,)), ((), ()))
_DOT_PV = (((1,), (0,)), ((), ()))


def _flash_fwd_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,  # blocked refs
    acc_ref,
    m_ref,
    l_ref,  # VMEM scratch
    *,
    sm_scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    n_kv: int,
    sq: int,
    skv: int,
):
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = jk * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    needed = jnp.logical_or(not causal, jk * block_k <= iq * block_q + block_q - 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, _DOT_QK, preferred_element_type=jnp.float32) * sm_scale
        mask = k_pos < skv  # kv padding
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        pv = jax.lax.dot_general(p, v, _DOT_PV, preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = m_new

    @pl.when(jk == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_fwd_pallas(
    q: jnp.ndarray,  # (BH, Sq, D)
    k: jnp.ndarray,  # (BH, Skv, D)
    v: jnp.ndarray,  # (BH, Skv, D)
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    bh, sq, d = q.shape
    skv = k.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    pad_q = (-sq) % block_q
    pad_k = (-skv) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0))) if pad_k else v
    nq = qp.shape[1] // block_q
    nk = kp.shape[1] // block_k
    kernel = functools.partial(
        _flash_fwd_kernel,
        sm_scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        n_kv=nk,
        sq=sq,
        skv=skv,
    )
    out = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, nq * block_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=pallas_interpret(interpret),
    )(qp, kp, vp)
    return out[:, :sq]


def flash_attention_pallas(
    q: jnp.ndarray,  # (B, Hq, Sq, D)
    k: jnp.ndarray,  # (B, Hk, Skv, D)
    v: jnp.ndarray,  # (B, Hk, Skv, D)
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """GQA wrapper: broadcasts KV heads, flattens (B, H) for the kernel."""
    b, hq, sq, d = q.shape
    hk = k.shape[1]
    g = hq // hk
    if g > 1:
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
    out = flash_attention_fwd_pallas(
        q.reshape(b * hq, sq, d),
        k.reshape(b * hq, -1, d),
        v.reshape(b * hq, -1, d),
        causal=causal,
        sm_scale=sm_scale,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
    )
    return out.reshape(b, hq, sq, d)
