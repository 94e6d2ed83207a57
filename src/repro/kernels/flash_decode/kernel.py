"""Pallas TPU flash-decode kernels: one query token vs a long KV cache.

Two kernels:

* ``flash_decode_pallas`` — contiguous cache.  Grid = (B*H, n_kv_blocks);
  KV blocks stream through VMEM while the (head_dim,) fp32 accumulator +
  scalar running max/sum persist in scratch.  Per-sequence valid lengths
  mask the tail block.
* ``paged_flash_decode_pallas`` — paged cache.  The KV pool stays put in
  HBM ((n_pages, Hk, page, d)); the per-sequence page table and valid
  lengths ride in as scalar-prefetch operands, and the grid iterates
  (B, page groups): one program covers one row's group of pages for all
  KV heads.  Each page slot of a group is a BlockSpec input of one
  physical page of every head, ``(1, Hk, page, d)``, whose index map
  resolves the logical page to its physical page through the prefetched
  table, so the pipeline DMAs exactly the group's pages into VMEM — the
  (B, Hk, P*page, d) gather the jnp fallback materializes never exists.
  Scores and context are head-batched MXU dots.  Groups entirely past a
  sequence's valid length are predicated off with ``pl.when`` (skipped by
  the scalar unit on TPU).
  An optional rotary/PE operand pair (q_pe, kpe pool) serves the MLA
  latent path: scores = q_lat*ckv + q_pe*kpe, context in latent space.

Both compose with cross-chip KV sharding via psum of (acc, m, l) partials
(see ops.sharded_decode_attention and the GSPMD path in
kernels/flash_attention/ops.decode_attention).
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

# dot_general dimension_numbers: contract the last axis of both operands
# (scores: q @ k^T) / contract q's last with v's first (context: p @ v)
_DOT_QK = (((1,), (1,)), ((), ()))
_DOT_PV = (((1,), (0,)), ((), ()))
# the same, batched over the leading (KV head) axis of both operands
_BDOT_QK = (((2,), (2,)), ((0,), (0,)))
_BDOT_PV = (((2,), (1,)), ((0,), (0,)))
_HIGHEST = jax.lax.Precision.HIGHEST


def _decode_kernel(
    len_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    sm_scale: float,
    block_k: int,
    n_kv: int,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)  # (1, d)
    k = k_ref[0].astype(jnp.float32)  # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, _DOT_QK, preferred_element_type=jnp.float32)[0] * sm_scale
    pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_k,), 0)
    valid = pos < len_ref[0]
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[0]
    m_new = jnp.maximum(m_prev, s.max())
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_ref[0] = l_ref[0] * alpha + p.sum()
    pv = jax.lax.dot_general(p[None], v, _DOT_PV, preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[0] = m_new

    @pl.when(j == n_kv - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[0], 1e-30))[0].astype(o_ref.dtype)


def flash_decode_pallas(
    q: jnp.ndarray,  # (B, H, D)
    k_cache: jnp.ndarray,  # (B, H, S, D) (GQA: broadcast KV heads first)
    v_cache: jnp.ndarray,  # (B, H, S, D)
    lengths: jnp.ndarray,  # (B,) int32
    *,
    sm_scale: Optional[float] = None,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    b, h, s, d = k_cache.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    block_k = min(block_k, s)
    pad = (-s) % block_k
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nk = k_cache.shape[2] // block_k
    qf = q.reshape(b * h, 1, d)
    kf = k_cache.reshape(b * h, -1, d)
    vf = v_cache.reshape(b * h, -1, d)
    lens = jnp.repeat(lengths.astype(jnp.int32), h)  # (B*H,)
    kernel = functools.partial(_decode_kernel, sm_scale=scale, block_k=block_k, n_kv=nk)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, nk),
        in_specs=[
            pl.BlockSpec((1,), lambda i, j: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, 1, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=pallas_interpret(interpret),
    )(lens, qf, kf, vf)
    return out.reshape(b, h, d)


def _head_dot(a, b, dims):
    """Head-batched MXU dot with float32 products and sums: bf16 operands
    in one pass (their products are exact in float32), float32 ones at
    ``Precision.HIGHEST``."""
    precision = _HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _paged_decode_kernel(
    *refs,
    sm_scale: float,
    page_size: int,
    pages_per_program: int,
    n_groups: int,
    has_pe: bool,
):
    """One (batch row, page group) program of paged flash decode, over every
    KV head of the row.

    ``refs`` layout (scalar-prefetch first, then operands, then scratch):
      pt_ref   (B, n_groups*ppp) int32 SMEM — the physical page each
                                     (group, slot) reads (the index maps')
      len_ref  (B,) int32 SMEM          — valid positions incl. new token
      q_ref    (1, Hk, G, dk) VMEM block
      [qpe_ref (1, Hk, G, dr) VMEM block]          (has_pe)
      ppp page tiles (1, Hk, page, dk) of the K pool, [ppp of the PE pool],
      then ppp of the V pool: page i of this group for all heads, fetched by
      its BlockSpec through the page table
      o_ref    (1, Hk, G, dv) VMEM block
      acc (Hk, G, dv), m (Hk, G, 1), l (Hk, G, 1) f32 running-stat scratch.

    Scores (Hk, G, blk) and context (Hk, G, dv) are head-batched MXU dots
    for every G, one query per head (MHA) included: on a v5e that beat the
    same products and sums on the VPU at the stablelm-1.6b serving shapes.
    """
    ppp = pages_per_program
    n_pools = 3 if has_pe else 2
    pt_ref, len_ref, q_ref = refs[:3]
    qpe_ref = refs[3] if has_pe else None
    tiles = refs[3 + has_pe: 3 + has_pe + n_pools * ppp]
    o_ref, acc_ref, m_ref, l_ref = refs[3 + has_pe + n_pools * ppp:]
    b = pl.program_id(0)
    grp = pl.program_id(1)

    @pl.when(grp == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    start = grp * ppp * page_size

    def block(p):
        # pool p's pages of this group for every head: one (Hk, blk, d) block
        return jnp.concatenate([t[0] for t in tiles[p * ppp: (p + 1) * ppp]], axis=1)

    def scores(q, k):
        if q.dtype != k.dtype:
            q, k = q.astype(jnp.float32), k.astype(jnp.float32)
        return _head_dot(q, k, _BDOT_QK)

    @pl.when(start < length)
    def _compute():
        s = scores(q_ref[0], block(0))  # (Hk, G, blk)
        if has_pe:
            s = s + scores(qpe_ref[0], block(1))
        s = s * sm_scale
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        valid = pos < length
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]  # (Hk, G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=2, keepdims=True)
        pv = _head_dot(p, block(n_pools - 1).astype(jnp.float32), _BDOT_PV)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(grp == n_groups - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_flash_decode_pallas(
    q: jnp.ndarray,  # (B, Hk, G, dk)
    k_pages: jnp.ndarray,  # (n_pages, Hk, page, dk) physical pool
    v_pages: jnp.ndarray,  # (n_pages, Hk, page, dv)
    lengths: jnp.ndarray,  # (B,) int32 valid positions incl. new token
    page_tables: jnp.ndarray,  # (B, pages_per_seq) int32 physical page ids
    *,
    q_pe: Optional[jnp.ndarray] = None,  # (B, Hk, G, dr)
    kpe_pages: Optional[jnp.ndarray] = None,  # (n_pages, Hk, page, dr)
    sm_scale: Optional[float] = None,
    pages_per_program: int = 8,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Paged-native flash decode: the pool is read in place (zero copy).

    Returns (B, Hk, G, dv).  The grid is (B, page groups): one program
    covers one row's group of ``pages_per_program`` pages for all KV heads.
    Each pool is passed once per page slot of a group, with a BlockSpec
    whose index map resolves that slot through the scalar-prefetched page
    table, so the pipeline DMAs exactly the group's pages into VMEM (and
    prefetches the next group's while this one computes).  A block is one
    physical page of every head, ``(1, Hk, page, d)``: it spans the pool's
    last three dims whole, so it is one contiguous run of HBM, and the TPU
    compiler takes it even where d is narrower than a 128-lane tile (it
    refuses a manual DMA of such a slice).  Groups past a row's length
    re-use the row's last group's block index, so they fetch nothing; the
    clamp is applied to the page table before the call, leaving each index
    map one scalar load.

    Shares its blocking (``pages_per_program`` pages = one score block) and
    float associativity with the jnp ``stream``/``gather`` implementations
    in ops.py, and like them multiplies and sums in float32; only the
    accumulation order inside a dot may differ, so outputs agree to float
    rounding, not bitwise.
    """
    b, hk, g, dk = q.shape
    n_pages, _, page_size, dv = v_pages.shape
    n_pp = page_tables.shape[1]
    has_pe = q_pe is not None
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(dk)
    ppp = max(1, min(pages_per_program, n_pp))
    padc = (-n_pp) % ppp
    if padc:  # pad with the scratch page; padded positions are masked out
        page_tables = jnp.pad(page_tables, ((0, 0), (0, padc)))
    n_groups = page_tables.shape[1] // ppp
    blk = ppp * page_size
    kernel = functools.partial(
        _paged_decode_kernel,
        sm_scale=scale,
        page_size=page_size,
        pages_per_program=ppp,
        n_groups=n_groups,
        has_pe=has_pe,
    )

    def row_block(width):
        return pl.BlockSpec((1, hk, g, width), lambda b_, g_, pt, ln: (b_, 0, 0, 0))

    # the page each (row, group, slot) program reads: groups past a row's
    # length re-read its last group, so their block indices do not change and
    # the pipeline fetches nothing for them.  Resolved here, not in the index
    # maps, which the pipeline evaluates for every slot of every program.
    last_group = jnp.maximum(lengths.astype(jnp.int32) - 1, 0) // blk
    group = jnp.minimum(jnp.arange(n_groups)[None, :], last_group[:, None])
    slots = (group[:, :, None] * ppp + jnp.arange(ppp)).reshape(b, n_groups * ppp)
    fetch_table = jnp.take_along_axis(page_tables.astype(jnp.int32), slots, axis=1)

    def page_block(width, i):
        return pl.BlockSpec((1, hk, page_size, width),
                            lambda b_, g_, pt, ln: (pt[b_, g_ * ppp + i], 0, 0, 0))

    pools = [k_pages, kpe_pages, v_pages] if has_pe else [k_pages, v_pages]
    q_specs = [row_block(dk)] + ([row_block(q_pe.shape[3])] if has_pe else [])
    page_specs = [page_block(pool.shape[3], i) for pool in pools for i in range(ppp)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_groups),
        in_specs=q_specs + page_specs,
        out_specs=row_block(dv),
        scratch_shapes=[
            pltpu.VMEM((hk, g, dv), jnp.float32),
            pltpu.VMEM((hk, g, 1), jnp.float32),
            pltpu.VMEM((hk, g, 1), jnp.float32),
        ],
    )
    operands = [fetch_table, lengths.astype(jnp.int32), q]
    operands += ([q_pe] if has_pe else []) + [pool for pool in pools for _ in range(ppp)]
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, g, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=pallas_interpret(interpret),
    )(*operands)
