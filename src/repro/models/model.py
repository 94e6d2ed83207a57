"""The LM: config-driven decoder supporting all 10 assigned architectures.

Layer stack = ``first_k_dense`` unrolled head layers + ``scan`` over
``n_periods`` repetitions of the arch's layer period (so 80-layer models
trace/compile one period, not 80 layers).  Period bodies are rematerialized
according to ``Runtime.remat``.

Three entry points (all pure functions of (params, inputs)):
  * ``loss_fn``     — next-token CE for training shapes
  * ``prefill``     — full-sequence forward, returns last-token logits + cache
  * ``decode_step`` — one token per sequence against the cache
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.models import blocks as blocks_mod
from repro.models.layers import (
    cast_to,
    embed_tokens,
    init_embedding,
    init_lm_head,
    init_rmsnorm,
    lm_logits,
    rms_norm,
    softmax_cross_entropy,
)
from repro.models.param import ann, split_tree, stack_periods
from repro.models.runtime import Runtime


def _tally(counts, layer_counts):
    return counts if layer_counts is None else counts + layer_counts


def _with_counts(out: Tuple, counts) -> Tuple:
    return out if counts is None else out + (counts,)


class LM:
    def __init__(self, cfg: ArchConfig, rt: Optional[Runtime] = None):
        self.cfg = cfg
        self.rt = rt or Runtime(remat="none")

    # ------------------------------------------------------------------
    # Parameters
    # ------------------------------------------------------------------
    def init_annotated(self, key: jax.Array):
        cfg = self.cfg
        keys = jax.random.split(key, 8)
        tree: Dict = {
            "embed": init_embedding(keys[0], cfg.vocab_size, cfg.d_model),
            "final_norm": init_rmsnorm(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            tree["lm_head"] = init_lm_head(keys[1], cfg.d_model, cfg.vocab_size)
        if cfg.frontend != "none":
            tree["frontend_proj"] = ann(
                jax.random.normal(keys[2], (cfg.d_model, cfg.d_model),
                                  jnp.float32) / math.sqrt(cfg.d_model),
                "embed", None)
        if cfg.first_k_dense:
            import dataclasses
            head_spec = dataclasses.replace(cfg.period[0], ffn="dense")
            hkeys = jax.random.split(keys[3], cfg.first_k_dense)
            tree["head_layers"] = tuple(
                blocks_mod.init_block(hkeys[i], cfg, head_spec)
                for i in range(cfg.first_k_dense))
        pkeys = jax.random.split(keys[4], max(cfg.n_periods, 1))

        def init_period(pkey):
            lkeys = jax.random.split(pkey, len(cfg.period))
            return {
                f"pos{i}": blocks_mod.init_block(lkeys[i], cfg, spec)
                for i, spec in enumerate(cfg.period)
            }

        # vmap builds the periods already stacked on the leading axis
        tree["periods"] = stack_periods(
            jax.vmap(init_period)(pkeys[:cfg.n_periods]))
        return tree

    def init(self, key: jax.Array, out_shardings=None):
        """Returns (param values pytree, logical axes pytree).

        The values come out of one jitted program, placed per
        ``out_shardings`` when given, so a full-width model is built where
        it will live and never holds its layer stack twice."""
        values = jax.jit(lambda k: split_tree(self.init_annotated(k))[0],
                         out_shardings=out_shardings)(key)
        return values, self.param_axes()

    def param_axes(self):
        """Axes tree without allocating parameters (eval_shape)."""
        annotated = jax.eval_shape(
            lambda: self.init_annotated(jax.random.PRNGKey(0)))
        return split_tree(annotated)[1]

    def param_shapes(self):
        """Param ShapeDtypeStruct tree without allocation."""
        annotated = jax.eval_shape(
            lambda: self.init_annotated(jax.random.PRNGKey(0)))
        return split_tree(annotated)[0]

    # ------------------------------------------------------------------
    # Shared stack application
    # ------------------------------------------------------------------
    def _head_spec(self):
        import dataclasses
        return dataclasses.replace(self.cfg.period[0], ffn="dense")

    def _embed_inputs(self, params, tokens: jnp.ndarray,
                      frontend_embeds: Optional[jnp.ndarray]):
        cfg, rt = self.cfg, self.rt
        x = embed_tokens(params["embed"], tokens, cfg.dtype)
        n_front = 0
        if cfg.frontend != "none":
            assert frontend_embeds is not None, f"{cfg.name} needs frontend_embeds"
            fe = cast_to(frontend_embeds, cfg.dtype) @ cast_to(
                params["frontend_proj"], cfg.dtype)
            x = jnp.concatenate([fe, x], axis=1)
            n_front = fe.shape[1]
        x = rt.constrain(x, ("batch", "seq", "act_embed")) if rt.rules else x
        return x, n_front

    def _apply_stack(self, params, x: jnp.ndarray, *, mode: str,
                     kv_lens: Optional[jnp.ndarray]):
        """mode in {train, prefill}; returns (hidden, cache, aux)."""
        cfg, rt = self.cfg, self.rt
        aux_total = jnp.zeros((), jnp.float32)
        head_caches = []
        for hp in params.get("head_layers", ()):
            x, c, aux = blocks_mod.apply_block(
                hp, x, cfg, self._head_spec(), rt, mode=mode, kv_lens=kv_lens)
            head_caches.append(c)
            aux_total = aux_total + aux

        def period_fn(carry, period_params):
            x, aux = carry
            caches = {}
            for i, spec in enumerate(cfg.period):
                x, c, aux_i = blocks_mod.apply_block(
                    period_params[f"pos{i}"], x, cfg, spec, rt,
                    mode=mode, kv_lens=kv_lens)
                caches[f"pos{i}"] = c if c is not None else 0
                aux = aux + aux_i
            return (x, aux), caches

        body = rt.remat_wrap(period_fn) if mode == "train" else period_fn
        (x, aux_total), period_caches = lax.scan(
            body, (x, aux_total), params["periods"])
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        cache = None
        if mode == "prefill":
            cache = {"head": tuple(head_caches), "periods": period_caches}
        return x, cache, aux_total

    # ------------------------------------------------------------------
    # Training
    # ------------------------------------------------------------------
    def loss_fn(self, params, batch: Dict) -> Tuple[jnp.ndarray, Dict]:
        """batch: tokens (B,S), labels (B,S) already shifted,
        optional frontend_embeds (B,F,d), optional loss_mask (B,S)."""
        cfg = self.cfg
        x, n_front = self._embed_inputs(params, batch["tokens"],
                                        batch.get("frontend_embeds"))
        hidden, _, aux = self._apply_stack(params, x, mode="train", kv_lens=None)
        hidden = hidden[:, n_front:]
        head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
        logits = lm_logits(head, hidden, cfg.dtype)
        if self.rt.rules is not None:
            logits = self.rt.constrain(logits, ("batch", "seq", "act_vocab"))
        ce = softmax_cross_entropy(logits, batch["labels"],
                                   batch.get("loss_mask"))
        loss = ce + aux
        return loss, {"ce": ce, "aux": aux,
                      "tokens": jnp.float32(batch["labels"].size)}

    # ------------------------------------------------------------------
    # Serving
    # ------------------------------------------------------------------
    def _no_counts(self):
        """Start of the serving steps' MoE work counts: zeros for a
        configuration with MoE layers, else None (no extra output)."""
        return jnp.zeros((2,), jnp.int32) if self.cfg.uses_moe else None

    def prefill(self, params, tokens: jnp.ndarray,
                frontend_embeds: Optional[jnp.ndarray] = None):
        cfg = self.cfg
        x, _ = self._embed_inputs(params, tokens, frontend_embeds)
        hidden, cache, _ = self._apply_stack(params, x, mode="prefill",
                                             kv_lens=None)
        head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
        logits_last = lm_logits(head, hidden[:, -1:], cfg.dtype)[:, 0]
        return logits_last, cache

    def init_cache(self, batch: int, max_seq: int):
        """Zero cache pytree (also used as the dry-run ShapeDtypeStruct
        template)."""
        cfg = self.cfg
        head = tuple(
            blocks_mod.init_block_cache(cfg, self._head_spec(), batch, max_seq)
            for _ in range(cfg.first_k_dense))

        def one_period():
            return {
                f"pos{i}": blocks_mod.init_block_cache(cfg, spec, batch, max_seq)
                for i, spec in enumerate(cfg.period)
            }

        periods = jax.tree.map(
            lambda *xs: jnp.stack(xs), *[one_period() for _ in range(cfg.n_periods)]
        ) if cfg.n_periods > 1 else jax.tree.map(
            lambda x: x[None], one_period())
        return {"head": head, "periods": periods}

    def cache_axes(self):
        """Logical axes pytree matching init_cache output."""
        cfg = self.cfg
        head = tuple(
            blocks_mod.block_cache_axes(cfg, self._head_spec())
            for _ in range(cfg.first_k_dense))
        period = {
            f"pos{i}": {k: ("layers",) + v for k, v in
                        blocks_mod.block_cache_axes(cfg, spec).items()}
            for i, spec in enumerate(cfg.period)
        }
        return {"head": head, "periods": period}

    def decode_step(self, params, tokens: jnp.ndarray, lengths: jnp.ndarray,
                    cache: Dict, frontend_embed: Optional[jnp.ndarray] = None):
        """tokens (B,) int32; lengths (B,) current cache fill.
        ``frontend_embed`` (B, d_model), when given, is projected through
        ``frontend_proj`` and decoded in place of the token embedding —
        teacher-forcing one frontend position (``tokens`` is ignored).
        Returns (logits (B,V), new_cache)."""
        cfg, rt = self.cfg, self.rt
        if frontend_embed is not None:
            x = cast_to(frontend_embed[:, None], cfg.dtype) @ cast_to(
                params["frontend_proj"], cfg.dtype)  # (B,1,d)
        else:
            x = embed_tokens(params["embed"], tokens[:, None], cfg.dtype)
        new_head = []
        for hp, hc in zip(params.get("head_layers", ()), cache["head"]):
            x, c = blocks_mod.apply_block_decode(
                hp, x, cfg, self._head_spec(), rt, hc, lengths)
            new_head.append(c)

        def period_fn(x, inputs):
            period_params, cache_in = inputs
            new_caches = {}
            for i, spec in enumerate(cfg.period):
                x, c = blocks_mod.apply_block_decode(
                    period_params[f"pos{i}"], x, cfg, spec, rt,
                    cache_in[f"pos{i}"], lengths)
                new_caches[f"pos{i}"] = c
            return x, new_caches

        x, new_periods = lax.scan(period_fn, x,
                                  (params["periods"], cache["periods"]))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
        logits = lm_logits(head, x[:, 0], cfg.dtype)
        return logits, {"head": tuple(new_head), "periods": new_periods}

    def prefill_chunk(self, params, tokens: jnp.ndarray,
                      n_valid: jnp.ndarray, cache: Dict,
                      page_tables: jnp.ndarray, *, s0: int):
        """One chunk of a chunked paged prefill (serving; attn-only archs).

        ``tokens`` (1, C) int32 is the chunk padded to the fixed chunk width
        C (fixed jit shape); ``n_valid`` () is how many of those are real;
        ``s0`` (static) is the absolute position of the chunk's first token.
        Each layer scatters the chunk's K/V (or latent) into the request's
        pages then attends causally with ``q_offset=s0`` over the gathered
        page row, so after the final chunk the pages and the last-position
        logits are bitwise those of a monolithic prefill (see DESIGN.md §11).
        Returns (logits (1, C, V), new_cache), and for a configuration with
        MoE layers a third output, the int32 [routed_rows, expert_rows]
        summed over them (``models/moe.py``)."""
        cfg, rt = self.cfg, self.rt
        x = embed_tokens(params["embed"], tokens, cfg.dtype)  # (1, C, d)
        counts = self._no_counts()
        new_head = []
        for hp, hc in zip(params.get("head_layers", ()), cache["head"]):
            x, c, n = blocks_mod.apply_block_prefill_paged(
                hp, x, cfg, self._head_spec(), rt, hc, n_valid, page_tables,
                s0=s0)
            new_head.append(c)
            counts = _tally(counts, n)

        def period_fn(carry, inputs):
            x, counts = carry
            period_params, cache_in = inputs
            new_caches = {}
            for i, spec in enumerate(cfg.period):
                x, c, n = blocks_mod.apply_block_prefill_paged(
                    period_params[f"pos{i}"], x, cfg, spec, rt,
                    cache_in[f"pos{i}"], n_valid, page_tables, s0=s0)
                new_caches[f"pos{i}"] = c
                counts = _tally(counts, n)
            return (x, counts), new_caches

        (x, counts), new_periods = lax.scan(
            period_fn, (x, counts), (params["periods"], cache["periods"]))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
        logits = lm_logits(head, x, cfg.dtype)  # (1, C, V)
        return _with_counts(
            (logits, {"head": tuple(new_head), "periods": new_periods}), counts)

    def decode_step_paged(self, params, tokens: jnp.ndarray,
                          lengths: jnp.ndarray, cache: Dict,
                          page_tables: jnp.ndarray):
        """Page-table-aware decode entry point (serving).

        ``cache`` mirrors ``init_cache`` but attention/MLA leaves are keyed by
        physical page ((n_pages, ..., page_size, ...), see
        ``repro.serve.cache.init_paged_cache``) and recurrent-state leaves by
        slot.  ``page_tables`` (B, pages_per_seq) int32 maps each sequence's
        logical pages to physical pages; page 0 is the scratch page that idle
        slots write into.  Attention over the pool runs the implementation
        ``Runtime.paged_impl`` names ("stream" paged-native jnp / "pallas"
        TPU kernel / the "gather" oracle bit-identical to stream — see
        kernels/flash_decode/ops.py; ``ServeEngine`` fills it in from the
        backend and mesh); ``Runtime.pages_per_program`` defaults to
        ``DEFAULT_PAGES_PER_PROGRAM`` there.  Returns (logits (B,V),
        new_cache), and for a configuration with MoE layers a third output
        as ``prefill_chunk`` does."""
        cfg, rt = self.cfg, self.rt
        x = embed_tokens(params["embed"], tokens[:, None], cfg.dtype)
        counts = self._no_counts()
        new_head = []
        for hp, hc in zip(params.get("head_layers", ()), cache["head"]):
            x, c, n = blocks_mod.apply_block_decode_paged(
                hp, x, cfg, self._head_spec(), rt, hc, lengths, page_tables)
            new_head.append(c)
            counts = _tally(counts, n)

        def period_fn(carry, inputs):
            x, counts = carry
            period_params, cache_in = inputs
            new_caches = {}
            for i, spec in enumerate(cfg.period):
                x, c, n = blocks_mod.apply_block_decode_paged(
                    period_params[f"pos{i}"], x, cfg, spec, rt,
                    cache_in[f"pos{i}"], lengths, page_tables)
                new_caches[f"pos{i}"] = c
                counts = _tally(counts, n)
            return (x, counts), new_caches

        (x, counts), new_periods = lax.scan(
            period_fn, (x, counts), (params["periods"], cache["periods"]))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
        logits = lm_logits(head, x[:, 0], cfg.dtype)
        return _with_counts(
            (logits, {"head": tuple(new_head), "periods": new_periods}), counts)
