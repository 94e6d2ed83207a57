"""Mixture-of-Experts FFN with expert parallelism.

Routing (top-k, optional renorm, shared experts) follows DeepSeek-MoE /
Jamba.  The dispatch-compute-combine path is written once and run two ways:

* **EP shard_map path** (production): experts are sharded over the ``model``
  mesh axis.  Because activations are tensor-parallel-replicated across
  ``model`` (every model shard already holds its data shard's tokens), the
  dispatch is *local* — each shard gathers the tokens routed to its resident
  experts into an (E_local, C, d) capacity buffer, runs the grouped SwiGLU,
  scatter-adds weighted outputs, and a single psum over ``model`` combines
  expert contributions (the same collective a TP FFN needs anyway).  This is
  the TPU-idiomatic EP layout: no all-to-all is required on the ICI torus,
  unlike GPU EP implementations that shard activations over the expert axis.
* **local path** (single host / smoke tests): identical math, E_local = E,
  no psum.

``MoEConfig.held_experts`` < E gives this chip the share one chip holds in
an expert-parallel deployment: the router still scores all E experts and
picks the top-k among them, but only experts 0 .. held-1 have weights here
and only their part of the result is computed (routed ids past them drop,
as in the EP path); the shared experts are added once.  Nothing stands in
for the other chips' experts or their exchange.

Capacity-overflow tokens are dropped per expert (standard Switch/GShard
semantics); the router aux loss keeps load balanced.

Each dispatch also counts its work: ``apply_moe`` returns, beside the
output, int32 ``[routed_rows, expert_rows]``: the (token, expert) pairs
routed to held experts within capacity, and the rows the expert FFN
computes (held experts x capacity).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.layers import cast_to
from repro.models.param import ann


def init_moe(key: jax.Array, cfg: ArchConfig) -> Dict:
    moe = cfg.moe
    d, e, f = cfg.d_model, moe.n_routed_experts, moe.expert_d_ff
    h = moe.n_held
    keys = jax.random.split(key, 7)
    s_in, s_ff = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    p = {
        "router": ann(jax.random.normal(keys[0], (d, e), jnp.float32) * s_in,
                      "embed", "expert"),
        "w_gate": ann(jax.random.normal(keys[1], (h, d, f), jnp.float32) * s_in,
                      "expert", "embed", "expert_mlp"),
        "w_up": ann(jax.random.normal(keys[2], (h, d, f), jnp.float32) * s_in,
                    "expert", "embed", "expert_mlp"),
        "w_down": ann(jax.random.normal(keys[3], (h, f, d), jnp.float32) * s_ff,
                      "expert", "expert_mlp", "embed"),
    }
    if moe.n_shared_experts:
        fs = moe.n_shared_experts * f
        p["sh_gate"] = ann(jax.random.normal(keys[4], (d, fs), jnp.float32) * s_in,
                           "embed", "mlp")
        p["sh_up"] = ann(jax.random.normal(keys[5], (d, fs), jnp.float32) * s_in,
                         "embed", "mlp")
        p["sh_down"] = ann(jax.random.normal(keys[6], (fs, d), jnp.float32)
                           / math.sqrt(fs), "mlp", "embed")
    return p


def _capacity(t: int, moe, train: bool) -> int:
    """Per-expert token capacity for a dispatch over ``t`` tokens.

    Training uses the standard Switch/GShard formula (overflow drops are the
    price of balanced static shapes).  Inference is fully dropless
    (``cap = t``, the worst case of every token routing to one expert): a
    token's output then never depends on which other tokens share its
    dispatch, so the same token at the same position produces bit-identical
    results whether it is processed by a B-row decode step, a B*T-row
    speculative verify step, or a prefill chunk of any size — the invariant
    the serve engine's spec-decode and chunked-prefill paths rely on.  (The
    previous eval rule, ``min(t, max(cap, 16))``, was dropless only for
    t <= 16 and silently coupled larger eval dispatches.)"""
    if not train:
        return max(t, 1)
    cap = int(math.ceil(t * moe.top_k / moe.n_routed_experts
                        * moe.capacity_factor))
    return max(cap, 1)


def _route(p: Dict, x: jnp.ndarray, cfg: ArchConfig, train: bool):
    """Router in fp32 over all E experts (at ``HIGHEST``: a TPU runs a
    float32 matmul in bfloat16 passes by default, and the gate's top-k is
    where rounding flips a choice).  x (B,S,d) -> ids (B,S,k) int32, probs
    (B,S,k) f32, aux."""
    moe = cfg.moe
    logits = jnp.matmul(x.astype(jnp.float32), p["router"].astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    probs_full = jax.nn.softmax(logits, axis=-1)
    probs, ids = lax.top_k(probs_full, moe.top_k)
    if moe.norm_topk:
        probs = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-9)
    aux = jnp.zeros((), jnp.float32)
    if train and moe.router_aux_loss > 0:
        # Switch-style load-balance loss: E * sum_e f_e * P_e with f_e the
        # fraction of routed assignments landing on expert e.
        e = moe.n_routed_experts
        me = probs_full.reshape(-1, e).mean(0)
        fe = jax.nn.one_hot(ids.reshape(-1), e, dtype=jnp.float32).mean(0)
        aux = e * jnp.sum(me * fe) * moe.router_aux_loss
    return ids.astype(jnp.int32), probs, aux


def _dispatch_compute_combine(
    xt: jnp.ndarray,       # (T, d) local tokens
    ids: jnp.ndarray,      # (T, k) global expert ids
    probs: jnp.ndarray,    # (T, k) f32
    wg: jnp.ndarray,       # (El, d, f) local experts
    wu: jnp.ndarray,
    wd: jnp.ndarray,
    e0: jnp.ndarray,       # scalar int: first local expert id
    capacity: int,
    dtype: str,
) -> jnp.ndarray:
    t, d = xt.shape
    k = ids.shape[1]
    el = wg.shape[0]
    c = capacity
    flat_ids = ids.reshape(-1)                       # (T*k,)
    local_ids = flat_ids - e0
    is_local = (local_ids >= 0) & (local_ids < el)
    a_ids = jnp.where(is_local, local_ids, el)       # el = drop bucket
    order = jnp.argsort(a_ids, stable=True)
    sorted_ids = a_ids[order]
    ar = jnp.arange(t * k, dtype=jnp.int32)
    is_new = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]])
    group_start = lax.cummax(jnp.where(is_new, ar, 0))
    rank = ar - group_start
    valid = (sorted_ids < el) & (rank < c)
    slot = jnp.where(valid, sorted_ids * c + rank, el * c)
    tok = order // k
    xbuf = jnp.zeros((el * c + 1, d), jnp.dtype(dtype)).at[slot].set(
        xt.astype(jnp.dtype(dtype))[tok])
    xe = xbuf[: el * c].reshape(el, c, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, cast_to(wg, dtype))) * \
        jnp.einsum("ecd,edf->ecf", xe, cast_to(wu, dtype))
    oe = jnp.einsum("ecf,efd->ecd", h, cast_to(wd, dtype)).reshape(el * c, d)
    w_sorted = probs.reshape(-1)[order].astype(jnp.float32)
    gathered = oe[jnp.where(valid, slot, 0)]
    contrib = gathered.astype(jnp.float32) * jnp.where(valid, w_sorted, 0.0)[:, None]
    y = jnp.zeros((t, d), jnp.float32).at[tok].add(contrib)
    return y.astype(jnp.dtype(dtype))


def _dispatch_2d(x_loc, xt_full, ids, probs, wg, wu, wd, e0, capacity,
                 dtype, spare_axes):
    """Replicated-token expert compute with d-sharded weights.

    x_loc (T, d_loc) is this shard's d-slice of the (replicated) tokens;
    wg/wu (El, d_loc, f) and wd (El, f, d_loc) keep their FSDP storage.
    Gate/up partials are psum'd over the spare axes BEFORE the
    nonlinearity; the down output stays d-sharded and is all-gathered
    (T x d bytes — tiny for decode) instead of gathering GBs of weights.
    """
    t, d_loc = x_loc.shape
    k = ids.shape[1]
    el, _, f = wg.shape
    c = capacity
    flat_ids = ids.reshape(-1)
    local_ids = flat_ids - e0
    is_local = (local_ids >= 0) & (local_ids < el)
    a_ids = jnp.where(is_local, local_ids, el)
    order = jnp.argsort(a_ids, stable=True)
    sorted_ids = a_ids[order]
    ar = jnp.arange(t * k, dtype=jnp.int32)
    is_new = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]])
    group_start = lax.cummax(jnp.where(is_new, ar, 0))
    rank = ar - group_start
    valid = (sorted_ids < el) & (rank < c)
    slot = jnp.where(valid, sorted_ids * c + rank, el * c)
    tok = order // k
    xbuf = jnp.zeros((el * c + 1, d_loc), jnp.dtype(dtype)).at[slot].set(
        x_loc.astype(jnp.dtype(dtype))[tok])
    xe = xbuf[: el * c].reshape(el, c, d_loc)
    g_part = jnp.einsum("ecd,edf->ecf", xe, cast_to(wg, dtype))
    u_part = jnp.einsum("ecd,edf->ecf", xe, cast_to(wu, dtype))
    g_full = lax.psum(g_part, spare_axes)
    u_full = lax.psum(u_part, spare_axes)
    h = jax.nn.silu(g_full) * u_full
    o_loc = jnp.einsum("ecf,efd->ecd", h, cast_to(wd, dtype)).reshape(
        el * c, d_loc)
    w_sorted = probs.reshape(-1)[order].astype(jnp.float32)
    gathered = o_loc[jnp.where(valid, slot, 0)]
    contrib = gathered.astype(jnp.float32) * jnp.where(
        valid, w_sorted, 0.0)[:, None]
    y_loc = jnp.zeros((t, d_loc), jnp.float32).at[tok].add(contrib)
    # reassemble full d on every shard (T x d — tiny for decode shapes)
    y = lax.all_gather(y_loc, spare_axes, axis=1, tiled=True)
    return y.astype(jnp.dtype(dtype))


def _shared_ffn(xt, sh_g, sh_u, sh_d, dtype) -> jnp.ndarray:
    xc = cast_to(xt, dtype)
    h = jax.nn.silu(xc @ cast_to(sh_g, dtype)) * (xc @ cast_to(sh_u, dtype))
    return h @ cast_to(sh_d, dtype)


def _counts(ids: jnp.ndarray, held: int, n_blocks: int,
            capacity: int) -> jnp.ndarray:
    """int32 [routed_rows, expert_rows] of ``n_blocks`` equal dispatches
    over ``ids`` (..., k): (token, expert) pairs on held experts that fit
    each block's capacity, and the held x capacity rows each block
    computes."""
    ids = ids.reshape(n_blocks, -1)
    block = jnp.arange(n_blocks, dtype=jnp.int32)[:, None]
    bucket = jnp.where(ids < held, block * held + ids, n_blocks * held)
    per_expert = jnp.bincount(bucket.reshape(-1), length=n_blocks * held + 1)
    routed = jnp.minimum(per_expert[:-1], capacity).sum()
    return jnp.stack([routed, jnp.asarray(n_blocks * held * capacity)]
                     ).astype(jnp.int32)


def apply_moe(
    p: Dict,
    x: jnp.ndarray,  # (B, S, d)
    cfg: ArchConfig,
    *,
    train: bool,
    mesh=None,
    rules=None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (y (B,S,d), aux_loss scalar, counts int32 [routed_rows,
    expert_rows])."""
    moe = cfg.moe
    b, s, d = x.shape
    held = moe.n_held
    ids, probs, aux = _route(p, x, cfg, train)
    has_shared = moe.n_shared_experts > 0
    use_shard_map = mesh is not None and rules is not None and \
        rules.model_axis() is not None

    if not use_shard_map:
        t = b * s
        cap = _capacity(t, moe, train)
        y = _dispatch_compute_combine(
            x.reshape(t, d), ids.reshape(t, -1), probs.reshape(t, -1),
            p["w_gate"], p["w_up"], p["w_down"], jnp.int32(0), cap, cfg.dtype)
        if has_shared:
            y = y + _shared_ffn(x.reshape(t, d), p["sh_gate"], p["sh_up"],
                                p["sh_down"], cfg.dtype)
        return y.reshape(b, s, d), aux, _counts(ids, held, 1, cap)

    model_axis = rules.model_axis()
    batch_axes = rules.batch_axes()
    n_model = rules.axis_sizes[model_axis]
    if held % n_model:
        raise ValueError(
            f"{cfg.name}: {held} held experts do not split over the "
            f"{n_model}-way {model_axis!r} mesh axis")
    n_blocks = math.prod(rules.axis_sizes[a] for a in batch_axes)
    counts = _counts(ids, held, n_blocks,
                     _capacity(b * s // n_blocks, moe, train))
    bspec = tuple(batch_axes) if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)
    # data axes NOT carrying the batch can carry the experts' d_model dim
    # (FSDP storage); with replicated tokens (latency-optimal decode) we
    # keep the weights fully sharded and psum partial activations instead
    # of gathering weights — see EXPERIMENTS.md §Perf.
    mesh_axes = tuple(mesh.axis_names)
    spare_axes = tuple(a for a in mesh_axes
                       if a != model_axis and a not in batch_axes)
    use_2d_experts = bool(spare_axes) and not batch_axes

    def fn(x_blk, ids_blk, probs_blk, wg, wu, wd, *shared):
        bl, sl, _ = x_blk.shape
        t = bl * sl
        el = wg.shape[0]
        j = lax.axis_index(model_axis)
        e0 = (j * el).astype(jnp.int32)
        cap = _capacity(t, moe, train)
        if use_2d_experts:
            # weights arrive d-sharded over the spare axes: slice the
            # replicated tokens to the matching d range, compute partials,
            # psum over the spare axes before the nonlinearity
            d_loc = wg.shape[1]
            i = lax.axis_index(spare_axes[0]) if len(spare_axes) == 1 else \
                lax.axis_index(spare_axes)
            xt = x_blk.reshape(t, d)
            x_loc = lax.dynamic_slice_in_dim(xt, i * d_loc, d_loc, axis=1)
            flat_ids = ids_blk.reshape(t, -1)
            probs_f = probs_blk.reshape(t, -1)
            y = _dispatch_2d(x_loc, xt, flat_ids, probs_f, wg, wu, wd, e0,
                             cap, cfg.dtype, spare_axes)
        else:
            y = _dispatch_compute_combine(
                x_blk.reshape(t, d), ids_blk.reshape(t, -1),
                probs_blk.reshape(t, -1), wg, wu, wd, e0, cap, cfg.dtype)
        if shared:
            sh_g, sh_u, sh_d = shared
            y = y + _shared_ffn(x_blk.reshape(t, d), sh_g, sh_u, sh_d, cfg.dtype)
        y = lax.psum(y, model_axis)
        return y.reshape(bl, sl, d)

    expert_w_spec = (P(model_axis, spare_axes if len(spare_axes) > 1 else
                       spare_axes[0], None) if use_2d_experts
                     else P(model_axis, None, None))
    expert_wd_spec = (P(model_axis, None, spare_axes if len(spare_axes) > 1
                        else spare_axes[0]) if use_2d_experts
                      else P(model_axis, None, None))
    in_specs = [
        P(bspec, None, None),          # x
        P(bspec, None, None),          # ids
        P(bspec, None, None),          # probs
        expert_w_spec,                 # w_gate
        expert_w_spec,                 # w_up
        expert_wd_spec,                # w_down
    ]
    args = [x, ids, probs, p["w_gate"], p["w_up"], p["w_down"]]
    if has_shared:
        in_specs += [P(None, model_axis), P(None, model_axis), P(model_axis, None)]
        args += [p["sh_gate"], p["sh_up"], p["sh_down"]]
    y = jax.shard_map(fn, mesh=mesh, in_specs=tuple(in_specs),
                      out_specs=P(bspec, None, None), check_vma=False)(*args)
    return y, aux, counts
