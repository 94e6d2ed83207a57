"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle,
plus the jnp flash path (used by models) vs the naive reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ops import decode_attention, flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.flash_decode.kernel import flash_decode_pallas
from repro.kernels import pallas_interpret
from repro.kernels.sdca.kernel import VMEM_CAPACITY, local_sdca_pallas, vmem_bytes
from repro.kernels.sdca.ops import local_sdca
from repro.kernels.sdca.ref import local_sdca_ref
from repro.kernels.ssm_scan.kernel import selective_scan_pallas
from repro.kernels.ssm_scan.ops import selective_scan, selective_scan_step
from repro.kernels.ssm_scan.ref import selective_scan_ref


def _tol(dtype):
    return 3e-2 if dtype == jnp.bfloat16 else 2e-4


# ---------------------------------------------------------------------------
# flash attention: jnp blocked path (what models run)
# ---------------------------------------------------------------------------
FLASH_CASES = [
    # b, hq, hk, sq, skv, d, causal, bq, bk, dtype
    (2, 4, 2, 37, 37, 16, True, 16, 16, jnp.float32),
    (1, 8, 8, 64, 64, 32, True, 32, 16, jnp.float32),
    (2, 4, 1, 33, 65, 16, False, 16, 32, jnp.float32),
    (1, 6, 2, 48, 48, 8, True, 16, 16, jnp.bfloat16),
    (1, 2, 2, 130, 130, 64, True, 64, 64, jnp.float32),
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_jnp_forward_and_grad(case):
    b, hq, hk, sq, skv, d, causal, bq, bk, dtype = case
    ks = jax.random.split(jax.random.PRNGKey(hash(case) % 2**31), 3)
    q = jax.random.normal(ks[0], (b, hq, sq, d), dtype)
    k = jax.random.normal(ks[1], (b, hk, skv, d), dtype)
    v = jax.random.normal(ks[2], (b, hk, skv, d), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=_tol(dtype))
    if dtype == jnp.float32:
        g1 = jax.grad(lambda a, b_, c: flash_attention(
            a, b_, c, causal=causal, block_q=bq, block_k=bk).sum(),
            argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda a, b_, c: attention_ref(
            a, b_, c, causal=causal).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=3e-4)


def test_flash_kv_lens_masking():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 2, 16, 8))
    k = jax.random.normal(ks[1], (2, 2, 24, 8))
    v = jax.random.normal(ks[2], (2, 2, 24, 8))
    lens = jnp.array([7.0, 24.0])
    out = flash_attention(q, k, v, causal=False, kv_lens=lens,
                          block_q=8, block_k=8)
    ref = attention_ref(q, k, v, causal=False, kv_lens=lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([1, 2, 4]),
       st.integers(8, 70), st.booleans())
def test_flash_jnp_property(seed, g, sq, causal):
    """Property: blocked flash == naive attention for random shapes."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    hk, d = 2, 8
    q = jax.random.normal(ks[0], (1, hk * g, sq, d))
    k = jax.random.normal(ks[1], (1, hk, sq, d))
    v = jax.random.normal(ks[2], (1, hk, sq, d))
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


# ---------------------------------------------------------------------------
# flash attention: Pallas kernel (interpret mode)
# ---------------------------------------------------------------------------
PALLAS_FLASH_CASES = [
    (2, 4, 2, 64, 32, True, jnp.float32),
    (1, 2, 2, 100, 16, True, jnp.float32),
    (2, 4, 4, 48, 32, False, jnp.bfloat16),
    (1, 8, 2, 128, 64, True, jnp.float32),
]


@pytest.mark.parametrize("case", PALLAS_FLASH_CASES)
def test_flash_pallas_kernel(case):
    b, hq, hk, s, d, causal, dtype = case
    ks = jax.random.split(jax.random.PRNGKey(abs(hash(case)) % 2**31), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hk, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hk, s, d), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, block_q=32,
                                 block_k=32, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=_tol(dtype))


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("s,lens", [(50, (31, 50)), (128, (1, 100))])
def test_decode_jnp_vs_ref(s, lens):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    b, hk, g, d = 2, 2, 3, 16
    q = jax.random.normal(ks[0], (b, hk * g, d))
    kc = jax.random.normal(ks[1], (b, hk, s, d))
    vc = jax.random.normal(ks[2], (b, hk, s, d))
    lengths = jnp.asarray(lens, jnp.int32)
    out = decode_attention(q, kc, vc, lengths)
    ref = attention_ref(q[:, :, None], kc, vc, causal=False,
                        kv_lens=lengths.astype(jnp.float32))[:, :, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_flash_decode_pallas_kernel():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    b, h, s, d = 2, 4, 200, 32
    q = jax.random.normal(ks[0], (b, h, d))
    kc = jax.random.normal(ks[1], (b, h, s, d))
    vc = jax.random.normal(ks[2], (b, h, s, d))
    lens = jnp.array([137, 200], jnp.int32)
    out = flash_decode_pallas(q, kc, vc, lens, block_k=64, interpret=True)
    ref = attention_ref(q[:, :, None], kc, vc, causal=False,
                        kv_lens=lens.astype(jnp.float32))[:, :, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


# ---------------------------------------------------------------------------
# selective scan
# ---------------------------------------------------------------------------
def _ssm_inputs(seed, bt, s, dn, n):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[1], (bt, s, dn))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (bt, s, dn)))
    A = -jnp.abs(jax.random.normal(ks[3], (dn, n))) - 0.1
    B = jax.random.normal(ks[4], (bt, s, n))
    C = jax.random.normal(ks[5], (bt, s, n))
    D = jnp.full((dn,), 0.4)
    return x, dt, A, B, C, D


@pytest.mark.parametrize("shape,chunk", [((2, 37, 8, 4), 8),
                                         ((1, 64, 16, 4), 16),
                                         ((2, 100, 4, 2), 32)])
def test_selective_scan_chunked_vs_ref(shape, chunk):
    bt, s, dn, n = shape
    x, dt, A, B, C, D = _ssm_inputs(s, bt, s, dn, n)
    y1, h1 = selective_scan(x, dt, A, B, C, D, chunk=chunk)
    y0, h0 = selective_scan_ref(x, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0), atol=1e-4)
    # gradients
    g1 = jax.grad(lambda *a: selective_scan(*a, D, chunk=chunk)[0].sum(),
                  argnums=(0, 1, 3))(x, dt, A, B, C)
    g0 = jax.grad(lambda *a: selective_scan_ref(*a, D)[0].sum(),
                  argnums=(0, 1, 3))(x, dt, A, B, C)
    for a, b in zip(g1, g0):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_selective_scan_pallas_kernel():
    bt, s, dn, n = 2, 70, 16, 4
    x, dt, A, B, C, D = _ssm_inputs(7, bt, s, dn, n)
    yk = selective_scan_pallas(x, dt, A, B, C, D, chunk=16, d_block=8,
                               interpret=True)
    yr, _ = selective_scan_ref(x, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), atol=1e-4)


def test_selective_scan_decode_step_consistency():
    bt, s, dn, n = 2, 12, 4, 3
    x, dt, A, B, C, D = _ssm_inputs(9, bt, s, dn, n)
    yref, _ = selective_scan_ref(x, dt, A, B, C, D)
    h = jnp.zeros((bt, dn, n))
    ys = []
    for t in range(s):
        y, h = selective_scan_step(x[:, t], dt[:, t], A, B[:, t], C[:, t], D, h)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, 1)),
                               np.asarray(yref), atol=1e-5)


# ---------------------------------------------------------------------------
# SDCA kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("sigma", [1.0, 4.0])
def test_sdca_pallas_vs_ref(sigma):
    m, nl, d, h = 3, 32, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    X = jax.random.normal(ks[0], (m, nl, d))
    y = jnp.sign(jax.random.normal(ks[1], (m, nl)))
    a = jnp.zeros((m, nl))
    w = jax.random.normal(ks[2], (d,)) * 0.1
    idx = jnp.stack([jax.random.permutation(k, nl)
                     for k in jax.random.split(ks[3], m)])
    ak, dwk = local_sdca_pallas(X, y, a, w, idx, sigma, 1e-3, float(m * nl),
                                interpret=True)
    ar, dwr = jax.vmap(lambda Xk, yk, ak_, ik: local_sdca_ref(
        Xk, yk, ak_, w, ik, sigma, 1e-3, float(m * nl)))(X, y, a, idx)
    np.testing.assert_allclose(np.asarray(ak), np.asarray(ar), atol=1e-4)
    np.testing.assert_allclose(np.asarray(dwk), np.asarray(dwr), atol=1e-4)


def test_local_sdca_refuses_a_shard_the_kernel_cannot_hold():
    """Asked for the kernel, ``local_sdca`` raises for a shard whose blocks
    exceed VMEM (CoCoA MNIST on 4 workers) instead of running the reference."""
    m, nl, d = 4, 15000, 784
    assert vmem_bytes(nl, d) > VMEM_CAPACITY
    args = (jax.ShapeDtypeStruct((m, nl, d), jnp.float32),
            jax.ShapeDtypeStruct((m, nl), jnp.float32),
            jax.ShapeDtypeStruct((m, nl), jnp.float32),
            jax.ShapeDtypeStruct((d,), jnp.float32),
            jax.ShapeDtypeStruct((m, nl), jnp.int32))
    with pytest.raises(ValueError, match="VMEM"):
        jax.eval_shape(lambda *a: local_sdca(*a, 1.0, 1e-4, 60000.0,
                                             use_pallas=True), *args)
    out = jax.eval_shape(lambda *a: local_sdca(*a, 1.0, 1e-4, 60000.0), *args)
    assert out[0].shape == (m, nl) and out[1].shape == (m, d)


def test_pallas_interpret_never_interprets_on_a_tpu(monkeypatch):
    assert pallas_interpret() is True  # the CPU backend here
    assert pallas_interpret(False) is False  # an AOT compile for a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_interpret() is False
    with pytest.raises(ValueError, match="TPU"):
        pallas_interpret(True)
