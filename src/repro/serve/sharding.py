"""Sharded serve data plane: ``Runtime`` + ``Rules`` -> placed tensors + jits.

This is the one place the serve engine meets a device mesh (DESIGN.md §13).
Given a ``Runtime`` carrying a mesh (and optionally explicit ``Rules`` —
``Rules.for_serving`` is the default policy: tensor parallelism over
"model", page pool and decode slots replicated), a :class:`ShardingPlan`

* places parameters with ``Rules.param_pspec`` over their logical axes;
* places the paged cache with ``Rules.act_pspec`` over ``LM.cache_axes()``
  — attention/MLA pools shard along their head/latent feature dims on the
  same mesh axes as the matching parameters, while the physical-page axis
  (``cache_batch``) stays replicated so any slot's page table can reference
  any page;
* compiles the decode / prefill-chunk jits with explicit in/out shardings
  (cache donated), so every step runs partitioned instead of relying on
  sharding propagation from whatever the last host write left behind.

Both jnp paged-attention implementations ("stream" and "gather") run under
the plan — they read the pool with gathers that partition trivially when the
page axis is replicated.  The "pallas" kernel is not partitioned: it runs on
a one-device mesh and is rejected at world size > 1.

The plan is geometry-only: it never copies weights itself until
``shard_params`` / ``shard_cache`` are called, so a CPU smoke engine on a
1x1 mesh pays one no-op ``device_put`` and is bitwise the unsharded engine.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.dist.partitioning import Rules
from repro.dist.treeutil import map_with_axes


def mesh_world_size(mesh) -> int:
    return int(mesh.devices.size) if mesh is not None else 1


def default_paged_impl(mesh) -> str:
    """Paged decode attention the serve engine runs unless told otherwise:
    the Pallas kernel on a TPU where the engine's state sits on one device,
    the paged-native jnp ``stream`` path on other backends and on larger
    meshes, where the kernel cannot be partitioned (see ``for_runtime``)."""
    if jax.default_backend() == "tpu" and mesh_world_size(mesh) == 1:
        return "pallas"
    return "stream"


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    """Placement of one serve engine's state on one mesh."""

    mesh: Any
    rules: Rules

    # ------------------------------------------------------------------
    @classmethod
    def for_runtime(cls, rt) -> Optional["ShardingPlan"]:
        """Plan for ``Runtime`` ``rt``; ``None`` when it carries no mesh."""
        if rt.mesh is None:
            return None
        rules = rt.rules or Rules.for_serving(rt.mesh)
        if rt.paged_impl == "pallas" and mesh_world_size(rt.mesh) > 1:
            raise ValueError(
                "paged_impl='pallas' is not partitioned; use 'stream' or "
                "'gather' on a multi-device mesh"
            )
        return cls(mesh=rt.mesh, rules=rules)

    # ------------------------------------------------------------------
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def param_sharding_tree(self, params: Any, param_axes: Any) -> Any:
        return map_with_axes(
            lambda leaf, ax: NamedSharding(
                self.mesh, self.rules.param_pspec(ax, tuple(leaf.shape))
            ),
            params,
            param_axes,
        )

    def cache_sharding_tree(self, cache: Any, cache_axes: Any) -> Any:
        """Shardings for a *paged* cache tree.  ``act_pspec`` resolves
        activation names first and falls back to parameter names (cache
        trees reuse e.g. "mamba_inner"); the shape-aware divisibility
        fallback leaves any non-dividing head/latent dim replicated."""
        return map_with_axes(
            lambda leaf, ax: NamedSharding(
                self.mesh, self.rules.act_pspec(ax, tuple(leaf.shape))
            ),
            cache,
            cache_axes,
        )

    # ------------------------------------------------------------------
    def shard_params(self, params: Any, param_axes: Any) -> Any:
        return jax.device_put(params, self.param_sharding_tree(params, param_axes))

    def shard_cache(self, cache: Any, cache_axes: Any) -> Any:
        return jax.device_put(cache, self.cache_sharding_tree(cache, cache_axes))

    def put_replicated(self, x: Any) -> Any:
        return jax.device_put(x, self.replicated())

    # ------------------------------------------------------------------
    def decode_jit(self, lm, params: Any, cache: Any):
        """``LM.decode_step_paged`` jitted with explicit shardings:
        (params, tokens, lengths, cache, page_tables) -> (logits, cache),
        cache donated, logits replicated (the engine argmaxes on host), and
        the MoE counts replicated where the model has them."""
        param_sh = self.param_sharding_tree(params, lm.param_axes())
        cache_sh = self.cache_sharding_tree(cache, lm.cache_axes())
        rep = self.replicated()
        return jax.jit(
            lm.decode_step_paged,
            in_shardings=(param_sh, rep, rep, cache_sh, rep),
            out_shardings=self._step_outputs(lm, cache_sh),
            donate_argnums=(3,),
        )

    def _step_outputs(self, lm, cache_sh):
        """Out shardings of a serving step: logits, cache, MoE counts."""
        rep = self.replicated()
        return (rep, cache_sh) + ((rep,) if lm.cfg.uses_moe else ())

    def prefill_chunk_jit(self, lm, params: Any, cache: Any):
        """``LM.prefill_chunk`` jitted with the same cache placement (chunk
        logits replicated; ``s0`` static as in the unsharded jit).  pjit
        rejects kwargs once ``in_shardings`` is given, so ``s0`` becomes a
        static *positional* under a wrapper keeping the engine's
        ``s0=``-kwarg call signature."""
        param_sh = self.param_sharding_tree(params, lm.param_axes())
        cache_sh = self.cache_sharding_tree(cache, lm.cache_axes())
        rep = self.replicated()
        jitted = jax.jit(
            lambda params, tokens, n_tokens, cache, rows, s0: lm.prefill_chunk(
                params, tokens, n_tokens, cache, rows, s0=s0
            ),
            static_argnums=(5,),
            in_shardings=(param_sh, rep, rep, cache_sh, rep),
            out_shardings=self._step_outputs(lm, cache_sh),
            donate_argnums=(3,),
        )

        def chunk(params, tokens, n_tokens, cache, rows, *, s0):
            return jitted(params, tokens, n_tokens, cache, rows, s0)

        return chunk
