"""Paged-native decode: bit-identity across implementations, physical
placement invariance, and the no-dense-KV jaxpr guarantee.

The stream and gather implementations share one blocking scheme and one
jnp op structure, so their outputs must match **bitwise** — under any
page table, any shared prefix pages, any ragged lengths, and any
pages_per_program.  That exactness is what lets the engine switch
implementations without perturbing prefix-cache guarantees (tested
end-to-end: a stream engine and a gather engine serve identical traces
token-for-token and logit-for-logit).  The Pallas kernel runs the same
blocked math with float32 products and sums and must match to float
exactness (its head-batched dots may accumulate in another order, so the
last ulp is not contractual)."""
import jax
import jax.extend.core as jax_core
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_decode.ops import (
    paged_decode_attention,
    paged_latent_decode_attention,
)
from repro.serve import ServeEngine

IMPLS = ("stream", "pallas", "gather")


def _assert_impls_agree(outs):
    """outs: dict impl -> np array.  stream == gather bitwise; pallas to
    float exactness (~1 ulp in f32; exact after a bf16 downcast)."""
    np.testing.assert_array_equal(outs["stream"], outs["gather"])
    atol = 1e-2 if outs["stream"].dtype == np.dtype("bfloat16") else 1e-6
    np.testing.assert_allclose(
        np.asarray(outs["pallas"], np.float32),
        np.asarray(outs["stream"], np.float32), atol=atol)


def _paged_inputs(seed, b=3, hk=2, g=2, d=16, page=8, npp=6, n_pages=32,
                  dtype=jnp.float32, share_prefix=True, lens=None):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, hk * g, d), dtype)
    kp = jnp.asarray(rng.randn(n_pages, hk, page, d), dtype)
    vp = jnp.asarray(rng.randn(n_pages, hk, page, d), dtype)
    pts = np.stack([rng.choice(n_pages, npp, replace=False)
                    for _ in range(b)])
    if share_prefix and b > 1:
        pts[1][:2] = pts[0][:2]  # two rows share their first two pages
    if lens is None:
        lens = [1 + rng.randint(npp * page) for _ in range(b)]
    lens = np.asarray(lens, np.int32)
    return q, kp, vp, jnp.asarray(lens), jnp.asarray(pts, jnp.int32)


def _ragged_lens(ppp, page, npp):
    """Rows ending on a group boundary, mid-page, on the row's capacity,
    and an idle row (length 1)."""
    return [ppp * page, ppp * page + 3, npp * page, 1]


# (pages_per_program, KV heads, G): the first three are small-GQA cases at
# random lengths; the rest run four KV heads, one query each (G == 1, the
# kernel's VPU math) and two (G > 1, its head-batched MXU dots), at the
# production blockings over ragged lengths
PAGED_CASES = [pytest.param(p, 2, 2, id=str(p)) for p in (1, 3, 6)] + [
    pytest.param(p, 4, g, id=f"{p}-hk4-g{g}") for g in (1, 2) for p in (4, 8, 16)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("ppp,hk,g", PAGED_CASES)
def test_paged_impls_bit_identical(dtype, ppp, hk, g):
    if hk == 2:
        q, kp, vp, lens, pt = _paged_inputs(0, dtype=dtype)
    else:
        page, npp = 8, 32
        q, kp, vp, lens, pt = _paged_inputs(
            ppp, b=4, hk=hk, g=g, page=page, npp=npp, n_pages=64, dtype=dtype,
            lens=_ragged_lens(ppp, page, npp))
    outs = {impl: np.asarray(paged_decode_attention(
        q, kp, vp, lens, pt, impl=impl, pages_per_program=ppp))
        for impl in IMPLS}
    _assert_impls_agree(outs)


@pytest.mark.parametrize("dtype,ppp", [
    pytest.param(jnp.float32, 2, id="float32"),
    pytest.param(jnp.bfloat16, 2, id="bfloat16")] + [
    pytest.param(jnp.bfloat16, p, id=f"bfloat16-ppp{p}") for p in (4, 8, 16)])
def test_paged_latent_impls_bit_identical(dtype, ppp):
    rng = np.random.RandomState(1)
    b, h, r, rope, page, n_pages = 3, 4, 16, 8, 8, 32
    npp = 6 if ppp == 2 else 16
    q_lat = jnp.asarray(rng.randn(b, h, r), dtype)
    q_pe = jnp.asarray(rng.randn(b, h, rope), dtype)
    ckv = jnp.asarray(rng.randn(n_pages, page, r), dtype)
    kpe = jnp.asarray(rng.randn(n_pages, page, rope), dtype)
    pts = np.stack([rng.choice(n_pages, npp, replace=False) for _ in range(b)])
    if ppp == 2:
        lens = [5, 17, 41]
    else:  # a group boundary, the row's capacity, an idle row; shared prefix
        pts[1][:2] = pts[0][:2]
        lens = [ppp * page, npp * page, 1]
    pt = jnp.asarray(pts, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    outs = {impl: np.asarray(paged_latent_decode_attention(
        q_lat, q_pe, ckv, kpe, lens, pt, sm_scale=0.2, impl=impl,
        pages_per_program=ppp)) for impl in IMPLS}
    _assert_impls_agree(outs)


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.sampled_from([1, 2, 4, 5]),
       st.sampled_from([1, 2, 3]), st.sampled_from([1, 2, 4]))
def test_paged_property_bit_identical(seed, ppp, g, hk):
    """Property: stream == gather bitwise (pallas to float exactness) for
    random page tables, shared prefix pages, ragged lengths, and KV head
    counts."""
    q, kp, vp, lens, pt = _paged_inputs(seed, hk=hk, g=g, npp=5)
    outs = {impl: np.asarray(paged_decode_attention(
        q, kp, vp, lens, pt, impl=impl, pages_per_program=ppp))
        for impl in IMPLS}
    _assert_impls_agree(outs)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_paged_physical_placement_invariance(seed):
    """Permuting the physical page pool (with the table re-pointed) must not
    change a single bit of the output — decode depends only on logical
    content, never on where pages landed."""
    q, kp, vp, lens, pt = _paged_inputs(seed)
    n_pages = kp.shape[0]
    rng = np.random.RandomState(seed + 1)
    perm = rng.permutation(n_pages)
    inv = np.argsort(perm)
    out = paged_decode_attention(q, kp, vp, lens, pt, impl="stream",
                                 pages_per_program=2)
    out_perm = paged_decode_attention(
        q, kp[jnp.asarray(perm)], vp[jnp.asarray(perm)], lens,
        jnp.asarray(inv[np.asarray(pt)], jnp.int32), impl="stream",
        pages_per_program=2)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_perm))


def test_paged_latent_matches_dense_softmax():
    """The blocked latent path agrees with a plain dense softmax over the
    gathered latent cache (numerical check, not bitwise)."""
    rng = np.random.RandomState(3)
    b, h, r, rope, page, npp, n_pages = 2, 4, 8, 4, 8, 4, 16
    q_lat = jnp.asarray(rng.randn(b, h, r), jnp.float32)
    q_pe = jnp.asarray(rng.randn(b, h, rope), jnp.float32)
    ckv = jnp.asarray(rng.randn(n_pages, page, r), jnp.float32)
    kpe = jnp.asarray(rng.randn(n_pages, page, rope), jnp.float32)
    pt = jnp.asarray(np.stack([rng.choice(n_pages, npp, replace=False)
                               for _ in range(b)]), jnp.int32)
    lens = jnp.asarray([9, 26], jnp.int32)
    out = paged_latent_decode_attention(q_lat, q_pe, ckv, kpe, lens, pt,
                                        sm_scale=0.3, impl="stream",
                                        pages_per_program=2)
    ckv_c = ckv[pt].reshape(b, npp * page, r)
    kpe_c = kpe[pt].reshape(b, npp * page, rope)
    s = (jnp.einsum("bhr,bsr->bhs", q_lat, ckv_c)
         + jnp.einsum("bhe,bse->bhs", q_pe, kpe_c)) * 0.3
    mask = jnp.arange(npp * page)[None, :] < lens[:, None]
    s = jnp.where(mask[:, None, :], s, -1e30)
    ref = jnp.einsum("bhs,bsr->bhr", jax.nn.softmax(s, axis=-1), ckv_c)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# ---------------------------------------------------------------------------
# the zero-copy guarantee, checked structurally
# ---------------------------------------------------------------------------
def _all_avals(jaxpr):
    """Every intermediate aval in a jaxpr, recursing into sub-jaxprs."""
    avals = []

    def subjaxprs(param):
        if isinstance(param, jax_core.ClosedJaxpr):
            yield param.jaxpr
        elif isinstance(param, jax_core.Jaxpr):
            yield param
        elif isinstance(param, (tuple, list)):
            for item in param:
                yield from subjaxprs(item)

    for eqn in jaxpr.eqns:
        avals.extend(v.aval for v in eqn.outvars)
        for p in eqn.params.values():
            for sub in subjaxprs(p):
                avals.extend(_all_avals(sub))
    return avals


def test_stream_jaxpr_has_no_dense_kv_intermediate():
    """The O(B*Hk*S*d) gather the legacy path materializes must be provably
    absent from the paged-native jaxpr: no intermediate anywhere carries
    the full cache-capacity sequence axis."""
    q, kp, vp, lens, pt = _paged_inputs(5, page=8, npp=20)  # capacity 160
    capacity = 20 * 8

    def dims(impl):
        jaxpr = jax.make_jaxpr(
            lambda *a: paged_decode_attention(*a, impl=impl,
                                              pages_per_program=2))(
            q, kp, vp, lens, pt).jaxpr
        return {d for aval in _all_avals(jaxpr)
                if hasattr(aval, "shape") for d in aval.shape}

    assert capacity in dims("gather"), "oracle must materialize the gather"
    assert capacity not in dims("stream"), (
        "paged-native stream path materialized a dense KV intermediate")


# ---------------------------------------------------------------------------
# engine-level equivalence (covers decode_step_paged + serve wiring)
# ---------------------------------------------------------------------------
GEOM = dict(smoke=True, max_batch=2, page_size=8, max_seq=64, seed=0)


def _run_trace(arch, paged_impl):
    eng = ServeEngine(arch, collect_logits=True, paged_impl=paged_impl,
                      **GEOM)
    rng = np.random.RandomState(11)
    head = rng.randint(0, 256, 16).astype(np.int32)
    reqs = [
        eng.submit(np.concatenate([head, rng.randint(0, 256, 5)
                                   .astype(np.int32)]), 5),
        eng.submit(rng.randint(0, 256, 9).astype(np.int32), 4,
                   arrival_step=2),
        eng.submit(np.concatenate([head, rng.randint(0, 256, 7)
                                   .astype(np.int32)]), 3, arrival_step=4),
    ]
    eng.run()
    return reqs


@pytest.mark.parametrize("arch", ["qwen3-14b", "deepseek-v2-236b"])
def test_engine_stream_vs_gather_bit_identical(arch):
    """A full continuous-batching trace (joins, prefix sharing, evictions)
    must be token- and logit-identical between the paged-native engine and
    the gather-oracle engine — for GQA and for the MLA latent path."""
    stream = _run_trace(arch, "stream")
    gather = _run_trace(arch, "gather")
    for rs, rg in zip(stream, gather):
        assert rs.generated == rg.generated
        for a, b in zip(rs.logits_trace, rg.logits_trace):
            np.testing.assert_array_equal(a, b)
