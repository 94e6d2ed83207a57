"""Pallas TPU kernel for the CoCoA local SDCA inner loop.

The paper's compute hot spot is "local learning": each worker runs H
sequential dual-coordinate updates over its (n_local, d) shard.  On GPU this
is a latency-bound pointer-chasing loop; the TPU adaptation keeps the whole
shard tile + the local model vector v resident in VMEM and runs the
sequential loop on-core — each update is one (d,)-dot + one (d,)-AXPY on the
VPU, with zero HBM traffic between updates.

Grid = (n_workers,): one program per worker (workers are embarrassingly
parallel within a BSP round).  The ops wrapper refuses a shard whose blocks
do not fit VMEM (``vmem_bytes``) rather than running something else.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_interpret


# VMEM of one v5e TensorCore; a shard whose blocks need more cannot run
VMEM_CAPACITY = 128 * 1024 * 1024


def vmem_bytes(nl: int, d: int) -> int:
    """VMEM the kernel's f32 blocks take, double-buffered, at TPU tile
    padding ((8, 128)): the shard, three (nl, 1) columns (y, a, a_out) and
    the (1, d) rows (w, dw, v)."""
    rows = -(-nl // 8) * 8
    lanes = -(-d // 128) * 128
    return 2 * 4 * (rows * lanes + 3 * rows * 128 + 3 * 8 * lanes)


def _sdca_kernel(
    x_ref,
    y_ref,
    a_ref,
    w_ref,
    idx_ref,
    a_out_ref,
    dw_ref,
    v_ref,
    *,
    h: int,
    sigma_prime: float,
    lam: float,
    n: float,
):
    # blocks: x (1, nl, d); y, a, a_out (1, nl, 1); w, dw (1, 1, d);
    # idx (1, 1, h) in SMEM; v (1, d) f32 scratch
    v_ref[...] = w_ref[0].astype(jnp.float32)
    a_out_ref[...] = a_ref[...]

    def step(t, _):
        j = idx_ref[0, 0, t]
        x = x_ref[0, pl.ds(j, 1), :].astype(jnp.float32)  # (1, d)
        yj = y_ref[0, pl.ds(j, 1), :].astype(jnp.float32)  # (1, 1)
        aj = a_out_ref[0, pl.ds(j, 1), :].astype(jnp.float32)
        xx = jnp.sum(x * x, keepdims=True)
        q = sigma_prime * xx / (lam * n)
        margin = yj * jnp.sum(v_ref[...] * x, keepdims=True)
        delta_raw = jnp.where(q > 0, (1.0 - margin) / jnp.maximum(q, 1e-30), 0.0)
        a_new = jnp.clip(aj + delta_raw, 0.0, 1.0)
        delta = jnp.where(xx > 0, a_new - aj, 0.0)
        a_out_ref[0, pl.ds(j, 1), :] = (aj + delta).astype(a_out_ref.dtype)
        v_ref[...] = v_ref[...] + sigma_prime * delta * yj * x / (lam * n)
        return 0

    jax.lax.fori_loop(0, h, step, 0)
    dw_ref[0] = ((v_ref[...] - w_ref[0].astype(jnp.float32)) / sigma_prime).astype(dw_ref.dtype)


def local_sdca_pallas(
    X: jnp.ndarray,  # (m, nl, d) worker shards
    y: jnp.ndarray,  # (m, nl)
    a: jnp.ndarray,  # (m, nl)
    w: jnp.ndarray,  # (d,)
    idx: jnp.ndarray,  # (m, H)
    sigma_prime: float,
    lam: float,
    n: float,
    *,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (new a (m, nl), dw (m, d)).

    Every block spans its array's last two dims whole (the TPU tiling rule
    for blocks narrower than a tile): the per-example vectors ride as
    (m, nl, 1) columns so a coordinate is a dynamic row, w / dw as
    (m, 1, d) rows, and the coordinate order as (m, 1, H)."""
    m, nl, d = X.shape
    h = idx.shape[1]
    w_b = jnp.broadcast_to(w[None, None], (m, 1, d))
    kernel = functools.partial(
        _sdca_kernel, h=h, sigma_prime=float(sigma_prime), lam=float(lam), n=float(n)
    )
    column = pl.BlockSpec((1, nl, 1), lambda i: (i, 0, 0))
    row = pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0))
    a_out, dw = pl.pallas_call(
        kernel,
        grid=(m,),
        in_specs=[
            pl.BlockSpec((1, nl, d), lambda i: (i, 0, 0)),
            column,
            column,
            row,
            pl.BlockSpec((1, 1, h), lambda i: (i, 0, 0), memory_space=pltpu.SMEM),
        ],
        out_specs=[column, row],
        out_shape=[
            jax.ShapeDtypeStruct((m, nl, 1), a.dtype),
            jax.ShapeDtypeStruct((m, 1, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=vmem_bytes(nl, d) + (4 << 20)
        ),
        interpret=pallas_interpret(interpret),
    )(X, y[..., None], a[..., None], w_b, idx.astype(jnp.int32)[:, None])
    return a_out[..., 0], dw[:, 0]
