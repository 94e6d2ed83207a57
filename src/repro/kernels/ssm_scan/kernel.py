"""Pallas TPU selective-scan kernel (Mamba-1), chunked over time.

Grid = (B, n_d_blocks, n_chunks); the chunk axis is innermost/sequential and
the (d_block, N) fp32 recurrent state persists in VMEM scratch across chunk
iterations.  Within a chunk the recurrence is stepped with a fori_loop over
time — each step is a (d_block, N) elementwise FMA on the VPU, with the
chunk's x/dt/B/C tiles already resident in VMEM, so HBM traffic is
O(S * (2*Dn + 2*N)) per batch element (the streaming minimum) instead of the
O(S * Dn * N) a naive materialized scan would move.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_interpret


def _ssm_kernel(
    x_ref,
    dt_ref,
    a_ref,
    b_ref,
    c_ref,
    d_ref,
    y_ref,
    h_ref,
    *,
    chunk: int,
    n_chunks: int,
    seq_len: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...].astype(jnp.float32)  # (bd, N)
    dvec = d_ref[...].astype(jnp.float32)  # (bd,)
    x = x_ref[0].astype(jnp.float32)  # (chunk, bd)
    dt = dt_ref[0].astype(jnp.float32)  # (chunk, bd)
    bmat = b_ref[0].astype(jnp.float32)  # (chunk, N)
    cmat = c_ref[0].astype(jnp.float32)  # (chunk, N)

    def step(t, carry):
        h, y = carry
        decay = jnp.exp(dt[t][:, None] * a)  # (bd, N)
        h = decay * h + (dt[t] * x[t])[:, None] * bmat[t][None, :]
        yt = jnp.sum(h * cmat[t][None, :], axis=1) + dvec * x[t]
        y = jax.lax.dynamic_update_slice(y, yt[None, :], (t, 0))
        return h, y

    y0 = jnp.zeros((chunk, x.shape[1]), jnp.float32)
    h, y = jax.lax.fori_loop(0, chunk, step, (h_ref[...], y0))
    h_ref[...] = h
    y_ref[0] = y.astype(y_ref.dtype)


def selective_scan_pallas(
    x: jnp.ndarray,  # (Bt, S, Dn)
    dt: jnp.ndarray,  # (Bt, S, Dn)
    A: jnp.ndarray,  # (Dn, N)
    B: jnp.ndarray,  # (Bt, S, N)
    C: jnp.ndarray,  # (Bt, S, N)
    D: jnp.ndarray,  # (Dn,)
    *,
    chunk: int = 128,
    d_block: int = 256,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    bt, s, dn = x.shape
    n = A.shape[1]
    chunk = min(chunk, s)
    d_block = min(d_block, dn)
    pad_s = (-s) % chunk
    pad_d = (-dn) % d_block
    padder = lambda z, ps, pd: jnp.pad(z, ((0, 0), (0, ps), (0, pd)))
    x_ = padder(x, pad_s, pad_d)
    dt_ = padder(dt, pad_s, pad_d)  # padded dt=0 -> decay=1, bx=0 (state held)
    B_ = padder(B, pad_s, 0)
    C_ = padder(C, pad_s, 0)
    A_ = jnp.pad(A, ((0, pad_d), (0, 0)))
    D_ = jnp.pad(D, (0, pad_d))
    nc = x_.shape[1] // chunk
    nd = x_.shape[2] // d_block
    kernel = functools.partial(_ssm_kernel, chunk=chunk, n_chunks=nc, seq_len=s)
    y = pl.pallas_call(
        kernel,
        grid=(bt, nd, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda b, di, ci: (b, ci, di)),
            pl.BlockSpec((1, chunk, d_block), lambda b, di, ci: (b, ci, di)),
            pl.BlockSpec((d_block, n), lambda b, di, ci: (di, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, di, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, di, ci: (b, ci, 0)),
            pl.BlockSpec((d_block,), lambda b, di, ci: (di,)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d_block), lambda b, di, ci: (b, ci, di)),
        out_shape=jax.ShapeDtypeStruct((bt, nc * chunk, nd * d_block), x.dtype),
        scratch_shapes=[pltpu.VMEM((d_block, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=pallas_interpret(interpret),
    )(x_, dt_, A_, B_, C_, D_)
    return y[:, :s, :dn]
