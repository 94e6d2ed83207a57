"""Runtime options threaded through model apply functions.

Everything performance-tunable (block sizes, remat, sharding rules, MLA
absorption, MoE path) lives here so §Perf hillclimbing changes only a
Runtime, never model code.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax

from repro.dist.partitioning import Rules, constrain


@dataclasses.dataclass(frozen=True)
class Runtime:
    mesh: Optional[object] = None          # jax.sharding.Mesh
    rules: Optional[Rules] = None
    block_q: int = 512
    block_k: int = 512
    scan_chunk: int = 128
    mla_absorb: bool = False
    remat: str = "full"                     # none | full | dots
    page_size: int = 16                     # paged-KV page length (serving)
    # paged decode implementation: "stream" (paged-native jnp), "pallas"
    # (the TPU kernel), "gather" (dense gather — the correctness oracle);
    # None lets ServeEngine pick from the backend and the mesh.  stream and
    # gather are bit-identical for the same pages_per_program, pallas agrees
    # to float rounding (see kernels/flash_decode/ops.py).
    paged_impl: Optional[str] = None
    pages_per_program: Optional[int] = None  # None -> default / named tune cache

    def constrain(self, x: jax.Array, axes) -> jax.Array:
        return constrain(x, self.rules, axes)

    @property
    def constrain_fn(self):
        return None if self.rules is None else self.constrain

    def remat_wrap(self, fn):
        if self.remat == "none":
            return fn
        if self.remat == "dots":
            return jax.checkpoint(
                fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        return jax.checkpoint(fn)


LOCAL_RUNTIME = Runtime(remat="none")
