"""Pallas TPU flash-decode kernels: one query token vs a long KV cache.

Two kernels:

* ``flash_decode_pallas`` — contiguous cache.  Grid = (B*H, n_kv_blocks);
  KV blocks stream through VMEM while the (head_dim,) fp32 accumulator +
  scalar running max/sum persist in scratch.  Per-sequence valid lengths
  mask the tail block.
* ``paged_flash_decode_pallas`` — paged cache.  The KV pool stays put in
  HBM ((n_pages, Hk, page, d)); the per-sequence page table and valid
  lengths ride in as scalar-prefetch operands, and the grid iterates
  (B, Hk, page groups).  Each page slot of a group is a BlockSpec input
  whose index map resolves the logical page to its physical page through
  the prefetched table, so the pipeline DMAs exactly the group's pages
  into VMEM — the (B, Hk, P*page, d) gather the jnp fallback materializes
  never exists.  Groups entirely past a sequence's valid length are
  predicated off with ``pl.when`` (skipped by the scalar unit on TPU).
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


def _paged_decode_kernel(
    *refs,
    sm_scale: float,
    page_size: int,
    pages_per_program: int,
    n_groups: int,
    has_pe: bool,
):
    """One (batch row, kv head, page group) program of paged flash decode.

    ``refs`` layout (scalar-prefetch first, then operands, then scratch):
      pt_ref   (B, n_pp_padded) int32 SMEM — logical -> physical page ids
      len_ref  (B,) int32 SMEM          — valid positions incl. new token
      q_ref    (1, 1, G, dk) VMEM block
      [qpe_ref (1, 1, G, dr) VMEM block]           (has_pe)
      ppp page tiles (1, 1, page, dk) of the K pool, [ppp of the PE pool],
      then ppp of the V pool: page i of this group, fetched by its BlockSpec
      through the page table
      o_ref    (1, 1, G, dv) VMEM block
      acc (G, dv), m (G, 1), l (G, 1) f32 running-stat scratch.
    """
    ppp = pages_per_program
    n_pools = 3 if has_pe else 2
    pt_ref, len_ref, q_ref = refs[:3]
    qpe_ref = refs[3] if has_pe else None
    tiles = refs[3 + has_pe: 3 + has_pe + n_pools * ppp]
    o_ref, acc_ref, m_ref, l_ref = refs[3 + has_pe + n_pools * ppp:]
    b = pl.program_id(0)
    grp = pl.program_id(2)

    @pl.when(grp == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    start = grp * ppp * page_size

    def block(p):
        # pool p's pages of this group as one (ppp*page, d) f32 block
        group = tiles[p * ppp: (p + 1) * ppp]
        return jnp.concatenate([t[0, 0].astype(jnp.float32) for t in group], axis=0)

    @pl.when(start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, dk)
        s = jax.lax.dot_general(q, block(0), _DOT_QK, precision=_HIGHEST,
                                preferred_element_type=jnp.float32)
        if has_pe:
            qpe = qpe_ref[0, 0].astype(jnp.float32)  # (G, dr)
            s = s + jax.lax.dot_general(qpe, block(1), _DOT_QK, precision=_HIGHEST,
                                        preferred_element_type=jnp.float32)
        s = s * sm_scale  # (G, blk)
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = pos < length
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]  # (G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(p, block(n_pools - 1), _DOT_PV, precision=_HIGHEST,
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(grp == n_groups - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


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
    pages_per_program: int = 4,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Paged-native flash decode: the pool is read in place (zero copy).

    Returns (B, Hk, G, dv).  Each pool is passed once per page slot of a
    group, with a BlockSpec whose index map resolves that slot through the
    scalar-prefetched page table, so the pipeline DMAs exactly the group's
    pages into VMEM (and prefetches the next group's while this one
    computes).  A (page, d) block spans the pool's last two dims whole,
    which the TPU compiler requires when d is narrower than a 128-lane tile
    (it refuses a manual DMA of such a slice).  Groups past a row's length
    re-use the row's last group's block index, so they fetch nothing.

    Shares its blocking (``pages_per_program`` pages = one score block) and
    float associativity with the jnp ``stream``/``gather`` implementations
    in ops.py, and like them runs every dot at ``Precision.HIGHEST``; only
    the accumulation order inside a dot may differ, so outputs agree to
    float rounding, not bitwise.
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

    def head_block(width):
        return pl.BlockSpec((1, 1, g, width), lambda b_, h_, g_, pt, ln: (b_, h_, 0, 0))

    def page_block(width, i):
        def index_map(b_, h_, g_, pt, ln):
            last = jnp.maximum(ln[b_] - 1, 0) // blk
            return (pt[b_, jnp.minimum(g_, last) * ppp + i], h_, 0, 0)

        return pl.BlockSpec((1, 1, page_size, width), index_map)

    pools = [k_pages, kpe_pages, v_pages] if has_pe else [k_pages, v_pages]
    q_specs = [head_block(dk)] + ([head_block(q_pe.shape[3])] if has_pe else [])
    page_specs = [page_block(pool.shape[3], i) for pool in pools for i in range(ppp)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hk, n_groups),
        in_specs=q_specs + page_specs,
        out_specs=head_block(dv),
        scratch_shapes=[
            pltpu.VMEM((g, dv), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    operands = [page_tables.astype(jnp.int32), lengths.astype(jnp.int32), q]
    operands += ([q_pe] if has_pe else []) + [pool for pool in pools for _ in range(ppp)]
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, g, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=pallas_interpret(interpret),
    )(*operands)
