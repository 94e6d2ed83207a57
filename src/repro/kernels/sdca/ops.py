"""jit'd wrapper for the local SDCA inner loop (kernel or jnp scan)."""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.sdca.kernel import VMEM_CAPACITY, local_sdca_pallas, vmem_bytes
from repro.kernels.sdca.ref import local_sdca_ref


def local_sdca(
    X: jnp.ndarray,  # (m, nl, d)
    y: jnp.ndarray,
    a: jnp.ndarray,
    w: jnp.ndarray,
    idx: jnp.ndarray,  # (m, H)
    sigma_prime: float,
    lam: float,
    n: float,
    *,
    use_pallas: bool = False,
    tuned: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    m, nl, d = X.shape
    if tuned:
        from repro.kernels.flash_decode.ops import _tuned_value

        shape = {"m": m, "nl": nl, "d": d, "h": idx.shape[1]}
        use_pallas = bool(_tuned_value("sdca", shape, X.dtype, "use_pallas", int(use_pallas)))
    if use_pallas:
        need = vmem_bytes(nl, d)
        if need > VMEM_CAPACITY:
            raise ValueError(
                f"SDCA kernel asked for a ({nl}, {d}) shard whose blocks need "
                f"{need} bytes of VMEM > {VMEM_CAPACITY}; use more workers"
            )
        return local_sdca_pallas(X, y, a, w, idx, sigma_prime, lam, n)

    def one_worker(Xk, yk, ak, ik):
        return local_sdca_ref(Xk, yk, ak, w, ik, sigma_prime, lam, n)

    new_a, dw = jax.vmap(one_worker)(X, y, a, idx)
    return new_a, dw
