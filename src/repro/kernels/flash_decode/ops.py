"""jit'd wrappers for decode attention: contiguous (kernel + jnp fallback)
and paged (three interchangeable implementations).

Paged decode reads the serve engine's physical page pool
((n_pages, Hk, page, d), see ``repro.serve.cache``) through a per-sequence
page table.  Three implementations share one blocking scheme
(``pages_per_program`` pages = one score block) and therefore one float
associativity.  ``stream`` and ``gather`` are **bit-identical** under any
page table / fill / blocking (tests assert it — this is what lets the
engine switch between them without perturbing prefix-cache guarantees);
the Pallas kernel computes the same blocked math with float32 products
and sums and matches them to float exactness (its head-batched dots may
accumulate in another order than the einsum, so the last ulp is not
contractual there):

* ``stream`` — paged-native jnp: a bounded loop gathers only the current
  group's pages ((B, ppp, Hk, page, d)) and runs an online softmax; the
  loop stops at ``max(lengths)``, so a step costs O(longest live sequence),
  not O(cache capacity).  No (B, Hk, P*page, d) dense KV intermediate ever
  exists in the jaxpr.  This is the engine's CPU path.
* ``pallas`` — ``paged_flash_decode_pallas``: same algorithm with the page
  table as a scalar-prefetch operand and pages streamed through VMEM, on a
  grid of (B, page groups): one program per row and group of
  ``pages_per_program`` pages, over all KV heads at once.
  This is the engine's path on a TPU; on the CPU it runs in interpret
  mode, which the tests use as its correctness proxy.
* ``gather`` — the legacy fallback and correctness oracle: materializes
  the full (B, Hk, P*page, d) gather, then runs the same blocked online
  softmax over it.  Pays the copy plus O(capacity) compute every step.

``pages_per_program`` defaults to ``DEFAULT_PAGES_PER_PROGRAM``, or to the
``repro.kernels.tune`` config cache entry for the call's (shape, dtype,
backend) key when a cache file was named (see ``tune.default_cache``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax.numpy as jnp
from jax import lax

from repro.kernels.flash_attention.ops import decode_attention, flash_attention
from repro.kernels.flash_decode.kernel import (
    flash_decode_pallas,
    paged_flash_decode_pallas,
)

NEG_INF = -1e30
PAGED_IMPLS = ("stream", "pallas", "gather")
# the fastest Pallas blocking of 4, 8 and 16 on a TPU v5e at stablelm-1.6b's
# serving shapes (4 ties it when a third of the rows are live)
DEFAULT_PAGES_PER_PROGRAM = 8
DEFAULT_PREFILL_CHUNK = 32


def decode_attention_auto(
    q: jnp.ndarray,  # (B, Hq, D)
    k_cache: jnp.ndarray,  # (B, Hk, S, D)
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    use_pallas: bool = False,
    block_k: int = 512,
    sm_scale: Optional[float] = None,
    tuned: bool = False,
) -> jnp.ndarray:
    """Dispatch decode attention to the Pallas kernel (TPU) or the jnp path
    (CPU / GSPMD-sharded caches).  ``tuned=True`` takes ``block_k`` from the
    autotuner's config cache when an entry exists."""
    if tuned:
        shape = {"b": q.shape[0], "h": q.shape[1], "s": k_cache.shape[2], "d": q.shape[2]}
        block_k = _tuned_value("flash_decode", shape, q.dtype, "block_k", block_k)
    if not use_pallas:
        return decode_attention(q, k_cache, v_cache, lengths, sm_scale=sm_scale)
    b, hq, d = q.shape
    hk = k_cache.shape[1]
    g = hq // hk
    if g > 1:
        k_cache = jnp.repeat(k_cache, g, axis=1)
        v_cache = jnp.repeat(v_cache, g, axis=1)
    return flash_decode_pallas(q, k_cache, v_cache, lengths, sm_scale=sm_scale, block_k=block_k)


# ---------------------------------------------------------------------------
# Paged decode: shared blocked core (stream / gather) + kernel dispatch
# ---------------------------------------------------------------------------
def _tuned_value(family: str, shape: dict, dtype, name: str, default):
    """Config-cache lookup (lazy import — tune imports this module's
    functions for sweeping)."""
    from repro.kernels.tune import lookup

    cfg = lookup(family, shape, dtype)
    if cfg and name in cfg:
        return int(cfg[name])
    return default


def _block_update(q, qpe, k_blk, kpe_blk, v_blk, start, length, scale, acc, m, l):
    """One online-softmax block update, shared op-for-op by ``stream`` and
    ``gather`` (and mirrored inside the Pallas kernel): q (..., G, dk),
    blocks (..., blk, d*), running stats acc (..., G, dv) / m, l (..., G)."""
    blk = k_blk.shape[-2]
    dot = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    s = dot("...gd,...pd->...gp", q, k_blk)
    if qpe is not None:
        s = s + dot("...gd,...pd->...gp", qpe, kpe_blk)
    s = s * scale
    pos = start + lax.broadcasted_iota(jnp.int32, (blk,), 0)
    valid = pos[None, :] < length[:, None]  # (B, blk)
    valid = valid[:, None, None, :]  # (B, 1, 1, blk) -> bcast Hk, G
    s = jnp.where(valid, s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
    l_new = l * alpha + p.sum(axis=-1)
    pv = dot("...gp,...pd->...gd", p, v_blk)
    acc_new = acc * alpha[..., None] + pv
    return acc_new, m_new, l_new


def _paged_prep(q, page_tables, pages_per_program, n_pp):
    ppp = max(1, min(int(pages_per_program), n_pp))
    padc = (-n_pp) % ppp
    if padc:  # pad with the scratch page; padded positions are masked out
        page_tables = jnp.pad(page_tables, ((0, 0), (0, padc)))
    return page_tables.astype(jnp.int32), ppp, page_tables.shape[1] // ppp


def _stream_core(q, qpe, k_pages, kpe_pages, v_pages, lengths, page_tables, scale, ppp, n_groups):
    """Paged-native jnp: per group, gather only that group's pages and run
    the shared block update; trip count is bounded by the longest live
    sequence, so no dense KV view is ever built."""
    b, hk, g, dk = q.shape
    page = k_pages.shape[2]
    dv = v_pages.shape[3]
    blk = ppp * page
    qf = q.astype(jnp.float32)
    qpef = None if qpe is None else qpe.astype(jnp.float32)
    lens = lengths.astype(jnp.int32)
    hi = jnp.minimum(lax.div(jnp.max(lens) + blk - 1, blk), n_groups)

    def group_step(j, carry):
        acc, m, l = carry
        pids = lax.dynamic_slice(page_tables, (0, j * ppp), (b, ppp))

        def blocked(pool):
            # (B, ppp, Hk, page, d) -> (B, Hk, ppp*page, d)
            tile = pool[pids]
            return jnp.moveaxis(tile, 2, 1).reshape(b, hk, blk, pool.shape[-1]).astype(jnp.float32)

        kpe_blk = None if kpe_pages is None else blocked(kpe_pages)
        k_blk, v_blk = blocked(k_pages), blocked(v_pages)
        return _block_update(qf, qpef, k_blk, kpe_blk, v_blk, j * blk, lens, scale, acc, m, l)

    init = (
        jnp.zeros((b, hk, g, dv), jnp.float32),
        jnp.full((b, hk, g), NEG_INF, jnp.float32),
        jnp.zeros((b, hk, g), jnp.float32),
    )
    acc, _, l = lax.fori_loop(0, hi, group_step, init)
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def _gather_core(q, qpe, k_pages, kpe_pages, v_pages, lengths, page_tables, scale, ppp, n_groups):
    """Gather oracle: materialize the dense (B, Hk, P*page, d) views — the
    O(B*Hk*S*d) per-step copy — then run the same blocked online softmax
    over every group regardless of fill."""
    b, hk, g, dk = q.shape
    page = k_pages.shape[2]
    dv = v_pages.shape[3]
    blk = ppp * page
    s_cap = n_groups * blk

    def full(pool):
        return jnp.moveaxis(pool[page_tables], 2, 1).reshape(b, hk, s_cap, pool.shape[-1])

    k_full, v_full = full(k_pages), full(v_pages)
    kpe_full = None if kpe_pages is None else full(kpe_pages)
    qf = q.astype(jnp.float32)
    qpef = None if qpe is None else qpe.astype(jnp.float32)
    lens = lengths.astype(jnp.int32)

    def group_step(carry, j):
        acc, m, l = carry

        def blocked(dense):
            sizes = (b, hk, blk, dense.shape[-1])
            return lax.dynamic_slice(dense, (0, 0, j * blk, 0), sizes).astype(jnp.float32)

        kpe_blk = None if kpe_full is None else blocked(kpe_full)
        k_blk, v_blk = blocked(k_full), blocked(v_full)
        carry = _block_update(qf, qpef, k_blk, kpe_blk, v_blk, j * blk, lens, scale, acc, m, l)
        return carry, None

    init = (
        jnp.zeros((b, hk, g, dv), jnp.float32),
        jnp.full((b, hk, g), NEG_INF, jnp.float32),
        jnp.zeros((b, hk, g), jnp.float32),
    )
    (acc, _, l), _ = lax.scan(group_step, init, jnp.arange(n_groups))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def _paged_dispatch(q, qpe, k_pages, kpe_pages, v_pages, lengths, page_tables, scale, impl, ppp):
    n_pp = page_tables.shape[1]
    page_tables, ppp, n_groups = _paged_prep(q, page_tables, ppp, n_pp)
    args = (q, qpe, k_pages, kpe_pages, v_pages, lengths, page_tables, scale, ppp, n_groups)
    if impl == "stream":
        return _stream_core(*args)
    if impl == "gather":
        return _gather_core(*args)
    if impl == "pallas":
        return paged_flash_decode_pallas(
            q,
            k_pages,
            v_pages,
            lengths,
            page_tables,
            q_pe=qpe,
            kpe_pages=kpe_pages,
            sm_scale=scale,
            pages_per_program=ppp,
        )
    raise ValueError(f"impl={impl!r} not in {PAGED_IMPLS}")


def paged_decode_attention(
    q: jnp.ndarray,  # (B, Hq, d) one new query token per sequence
    k_pages: jnp.ndarray,  # (n_pages, Hk, page, d) physical page pool
    v_pages: jnp.ndarray,  # (n_pages, Hk, page, d)
    lengths: jnp.ndarray,  # (B,) valid positions incl. the new token
    page_tables: jnp.ndarray,  # (B, pages_per_seq) int32
    *,
    sm_scale: Optional[float] = None,
    impl: str = "stream",
    pages_per_program: Optional[int] = None,
) -> jnp.ndarray:
    """GQA decode attention over the paged KV pool; returns (B, Hq, d).

    ``pages_per_program=None`` consults the autotuner's config cache for
    this (shape, dtype, backend) key, falling back to
    ``DEFAULT_PAGES_PER_PROGRAM``."""
    b, hq, d = q.shape
    hk, page = k_pages.shape[1], k_pages.shape[2]
    g = hq // hk
    if hq % hk:
        raise ValueError(f"Hq={hq} not a multiple of Hk={hk}")
    scale = sm_scale if sm_scale is not None else 1.0 / (d**0.5)
    if pages_per_program is None:
        shape = {"b": b, "hk": hk, "g": g, "d": d, "page": page, "npp": page_tables.shape[1]}
        pages_per_program = _tuned_value(
            "flash_decode_paged", shape, q.dtype, "pages_per_program", DEFAULT_PAGES_PER_PROGRAM
        )
    q4 = q.reshape(b, hk, g, d)
    out = _paged_dispatch(
        q4,
        None,
        k_pages,
        None,
        v_pages,
        lengths,
        page_tables,
        scale,
        impl,
        pages_per_program,
    )
    return out.reshape(b, hq, d)


def gather_pages(pool: jnp.ndarray, page_tables: jnp.ndarray) -> jnp.ndarray:
    """Dense per-sequence view of a page pool.

    ``pool`` is page-major with the page-position axis at index 2 of the
    gathered tile ((n_pages, ..., page, ...) with one leading page axis);
    ``page_tables`` is (B, pages_per_seq).  Returns
    (B, ..., pages_per_seq * page, ...): the contiguous cache view a
    chunked-prefill flash call attends over.  Positions past a sequence's
    fill hold stale/zero pages (including the scratch page) and must be
    masked by the caller via ``kv_lens``."""
    b, npp = page_tables.shape
    tile = pool[page_tables]  # (B, npp, ..., page, ...)
    if pool.ndim == 4:  # (n_pages, Hk, page, d) K/V pools
        return jnp.moveaxis(tile, 2, 1).reshape(
            b, pool.shape[1], npp * pool.shape[2], pool.shape[3])
    if pool.ndim == 3:  # (n_pages, page, r) MLA latent pools
        return tile.reshape(b, npp * pool.shape[1], pool.shape[2])
    raise ValueError(f"unsupported pool rank {pool.ndim}")


def paged_prefill_attention(
    q: jnp.ndarray,  # (B, Hq, C, d) one prompt chunk of queries
    k_pages: jnp.ndarray,  # (n_pages, Hk, page, d) pool incl. this chunk's K
    v_pages: jnp.ndarray,  # (n_pages, Hk, page, d)
    kv_lens: jnp.ndarray,  # (B,) valid positions incl. this chunk
    page_tables: jnp.ndarray,  # (B, pages_per_seq) int32
    *,
    q_offset: int,  # absolute position of the chunk's first query (static)
    sm_scale: Optional[float] = None,
    block_q: int = 16,
    block_k: int = 16,
) -> jnp.ndarray:
    """Causal chunked-prefill attention over the paged KV pool.

    The chunk's K/V must already be scattered into the pages (scatter then
    attend, exactly like the decode path); this gathers the whole page-table
    row to a contiguous view and runs the blocked flash forward with the
    chunk's absolute query offset.  Bit-identity with a monolithic prefill
    at the same ``block_k`` holds because (a) key blocks tile absolute
    positions from 0 regardless of the chunk boundary, (b) each query row's
    online-softmax accumulation is independent of how queries are blocked,
    and (c) positions at or past ``kv_lens`` are exact no-ops in the block
    update.  See DESIGN.md §11."""
    k_full = gather_pages(k_pages, page_tables)
    v_full = gather_pages(v_pages, page_tables)
    return flash_attention(
        q, k_full, v_full, causal=True, sm_scale=sm_scale,
        kv_lens=kv_lens.astype(jnp.float32), q_offset=q_offset,
        block_q=block_q, block_k=block_k)


def fold_verify_batch(
    tokens: jnp.ndarray,  # (B, T) row 0 = pending token, rows 1.. = drafts
    lengths: jnp.ndarray,  # (B,) committed fill per sequence
    page_tables: jnp.ndarray,  # (B, pages_per_seq)
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fold a (B, T) speculative verify window into a (B*T,) decode batch.

    Row ``s*T + t`` carries draft position ``t`` of sequence ``s``: token
    ``tokens[s, t]`` at cache position ``lengths[s] + t``, reading sequence
    ``s``'s page-table row.  Because every decode layer scatters all folded
    rows' K/V before attending, row ``t`` sees rows ``< t`` of its own
    sequence through its length mask — one batched target step verifies the
    whole window, and each row's output is bit-identical to the sequential
    one-token step that would have produced it (same math per row; extra
    rows only add exact masked no-ops).  Returns
    (tokens (B*T,), lengths (B*T,), page_tables (B*T, pages_per_seq))."""
    b, t = tokens.shape
    toks = tokens.reshape(b * t)
    lens = (lengths[:, None] + jnp.arange(t, dtype=lengths.dtype)[None, :]
            ).reshape(b * t)
    pts = jnp.repeat(page_tables, t, axis=0)
    return toks, lens, pts


def paged_verify_attention(
    q: jnp.ndarray,  # (B, T, Hq, d) draft-window queries
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    lengths: jnp.ndarray,  # (B,) fill BEFORE the window (row t attends l+t+1)
    page_tables: jnp.ndarray,  # (B, pages_per_seq)
    *,
    sm_scale: Optional[float] = None,
    impl: str = "stream",
    pages_per_program: Optional[int] = None,
) -> jnp.ndarray:
    """Multi-query verify over pages: decode attention for T draft positions
    per sequence in one call, by folding the window into the batch axis with
    ragged lengths (row t of sequence s attends ``lengths[s] + t + 1``
    positions).  The fold is exactly ``fold_verify_batch`` minus the token
    column, so outputs are bit-identical to T sequential decode calls.
    Returns (B, T, Hq, d)."""
    b, t, hq, d = q.shape
    lens = (lengths[:, None] + 1 + jnp.arange(t, dtype=lengths.dtype)[None, :]
            ).reshape(b * t)
    pts = jnp.repeat(page_tables, t, axis=0)
    out = paged_decode_attention(
        q.reshape(b * t, hq, d), k_pages, v_pages, lens, pts,
        sm_scale=sm_scale, impl=impl, pages_per_program=pages_per_program)
    return out.reshape(b, t, hq, d)


def paged_latent_decode_attention(
    q_lat: jnp.ndarray,  # (B, H, r) absorbed queries (latent space)
    q_pe: jnp.ndarray,  # (B, H, rope)
    ckv_pages: jnp.ndarray,  # (n_pages, page, r) latent page pool
    kpe_pages: jnp.ndarray,  # (n_pages, page, rope)
    lengths: jnp.ndarray,  # (B,) valid positions incl. the new token
    page_tables: jnp.ndarray,  # (B, pages_per_seq) int32
    *,
    sm_scale: float,
    impl: str = "stream",
    pages_per_program: Optional[int] = None,
) -> jnp.ndarray:
    """MLA latent decode over paged (c_kv, k_pe) pools; returns latent
    context (B, H, r).  scores = q_lat*ckv + q_pe*kpe; context accumulates
    against ckv directly (absorbed form), so the pools are both the keys
    and the values — zero re-expansion, zero gather in the non-oracle
    impls.  The size-1 head axis inserted below is a reshape (no copy)."""
    b, h, r = q_lat.shape
    page, npp = ckv_pages.shape[1], page_tables.shape[1]
    if pages_per_program is None:
        shape = {"b": b, "hk": 1, "g": h, "d": r, "page": page, "npp": npp}
        default = DEFAULT_PAGES_PER_PROGRAM
        pages_per_program = _tuned_value(
            "flash_decode_paged", shape, q_lat.dtype, "pages_per_program", default
        )
    out = _paged_dispatch(
        q_lat[:, None],
        q_pe[:, None],
        ckv_pages[:, None],
        kpe_pages[:, None],
        ckv_pages[:, None],
        lengths,
        page_tables,
        sm_scale,
        impl,
        pages_per_program,
    )
    return out[:, 0]
