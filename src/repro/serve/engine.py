"""Continuous-batching serve engine over the paged KV/state cache.

One fixed-shape jitted decode step serves every request: each decode slot
contributes one token per step, idle slots point at the scratch page, and
requests join (after a prefill writes their pages) or leave between steps
without draining the batch.  Greedy decoding only.

Two optional step-loop extensions (attention-only archs; see DESIGN.md §11):

* **Chunked prefill** (``prefill_chunk=C``): prompts stream into their pages
  ``C`` tokens per engine step instead of one monolithic batch-1 prefill, so
  a burst of long prompts no longer stalls the running decode batch and
  join-to-first-token p99 is bounded by ``ceil(P/C)`` steps rather than one
  arbitrarily long prefill.  Each chunk is causally masked with a static
  ``q_offset`` so the final pages and logits are bitwise a monolithic
  prefill's.
* **Speculative multi-token decode** (``speculate=k``): an n-gram /
  prefix-cache proposer (``repro.serve.speculate``) drafts up to ``k``
  tokens per slot, verified by ONE batched target step over the paged pools
  (the decode jit retraced at ``max_batch*(k+1)`` folded rows).  The
  accept-longest-prefix rule commits exactly the tokens greedy one-at-a-time
  decode would emit — drafts change step count, never output bits.

Time is measured in decode steps; a request's ``arrival_step`` gates its
admission, which keeps traces deterministic.  Per-step telemetry
``(active_batch, step_seconds, kind, committed)`` feeds the
``CapacityPlanner`` (``repro.serve.planner``) — the serve-side analogue of
the training f(m) loop.

Determinism notes: with a dense architecture every slot's computation is
independent of the other slots' contents, so a request's token trajectory is
bit-identical whether it runs alone or joins a busy batch of the same shape
(``max_batch`` and page geometry fixed).  MoE eval is dropless (capacity =
tokens, see models/moe.py), so per-token expert outputs are independent of
the dispatch size and the guarantee extends to folded verify batches.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import time
from contextlib import nullcontext
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.models.model import LM
from repro.models.runtime import Runtime
from repro.serve.cache import (
    init_paged_cache,
    max_pages_per_seq,
    restore_state,
    snapshot_state,
    write_prefill,
)
from repro.serve.paging import SCRATCH_PAGE, PagePool
from repro.serve.prefix import PrefixCache
from repro.serve.scheduler import Request, RequestState, Scheduler
from repro.serve.sharding import ShardingPlan, default_paged_impl, mesh_world_size
from repro.serve.speculate import NgramProposer
from repro.telemetry import (
    Event,
    MemorySink,
    ServeStepEvent,
    Tracker,
    warn_deprecated,
)
from repro.telemetry.trace import SpanTracer

logger = logging.getLogger(__name__)


class ServeEngine:
    """Continuous-batching engine for one model replica.

    ``params`` reuses weights another engine already holds (placed onto
    this engine's devices when its Runtime carries a mesh); by default
    they are built from ``seed``.  ``paged_impl`` forces a decode-attention
    implementation; by default it is the Pallas kernel on a TPU whose state
    sits on one device, and the paged-native jnp path otherwise."""

    def __init__(
        self,
        arch: str,
        *,
        smoke: bool = True,
        max_batch: int = 8,
        page_size: int = 16,
        max_seq: int = 256,
        num_pages: Optional[int] = None,
        seed: int = 0,
        params: Optional[Dict] = None,
        prefix_caching: bool = True,
        collect_logits: bool = False,
        rt: Optional[Runtime] = None,
        paged_impl: Optional[str] = None,
        prefill_chunk: Optional[int] = None,
        speculate: int = 0,
        draft_ngram: int = 3,
        replica_id: int = -1,
        trace: bool = False,
        trace_clock: Optional[Callable[[], float]] = None,
    ):
        self.cfg = self.config_for(arch, smoke)
        if speculate < 0:
            raise ValueError(f"speculate must be >= 0, got {speculate}")
        if (prefill_chunk is not None or speculate) and any(
            spec.mixer != "attn" for spec in self.cfg.period
        ):
            raise ValueError(
                "chunked prefill / speculative decode require attention-only "
                f"architectures; {self.cfg.name} has recurrent-state layers "
                "whose slot-major cache has no paged/positional form"
            )
        self.seed = seed
        rt = rt or self.default_runtime(page_size)
        if rt.page_size != page_size:
            raise ValueError("Runtime.page_size must match engine page_size")
        # the decode-attention implementation: an explicit paged_impl wins
        # over the Runtime's, and with neither the backend and mesh decide
        impl = paged_impl or rt.paged_impl or default_paged_impl(rt.mesh)
        self.rt = dataclasses.replace(rt, paged_impl=impl)
        logger.info(
            "%s: paged decode impl=%s (backend=%s, mesh devices=%d)",
            self.cfg.name, impl, jax.default_backend(), mesh_world_size(rt.mesh),
        )
        self.lm = LM(self.cfg, self.rt)
        # sharded data plane (DESIGN.md §13): when the Runtime carries a
        # mesh, params and the paged cache are placed per the serving Rules
        # (a one-device mesh pins the engine to that device) and the
        # decode/chunk jits are explicitly sharded.  The host-side step loop
        # is untouched — tokens/lengths/page tables are replicated, and the
        # eager cache writers (write_prefill, restore_state) hand arrays
        # back to the jit, whose in_shardings re-pin them.
        self.plan = ShardingPlan.for_runtime(self.rt)
        param_axes = self.lm.param_axes()
        if params is None:
            shardings = None
            if self.plan is not None:
                shardings = self.plan.param_sharding_tree(
                    self.lm.param_shapes(), param_axes
                )
            params, _ = self.lm.init(jax.random.PRNGKey(seed), shardings)
        elif self.plan is not None:
            params = self.plan.shard_params(params, param_axes)
        self.params = params
        self.max_batch = max_batch
        self.page_size = page_size
        self.max_seq = max_seq
        self.pages_per_seq = max_pages_per_seq(max_seq, page_size)
        if num_pages is None:
            num_pages = 1 + max_batch * self.pages_per_seq
        self.pool = PagePool(num_pages, page_size)
        self.prefix = PrefixCache(page_size) if prefix_caching else None
        self.prefill_chunk = prefill_chunk
        self.speculate = speculate
        self.proposer = (
            NgramProposer(draft_ngram, prefix_cache=self.prefix)
            if speculate
            else None
        )
        self.scheduler = Scheduler(
            max_batch,
            self.pool,
            prefix_cache=self.prefix,
            n_frontend_tokens=self.cfg.n_frontend_tokens,
            prefill_chunk=prefill_chunk,
        )
        self.collect_logits = collect_logits
        self.axes = self.lm.cache_axes()
        make_cache = functools.partial(
            init_paged_cache,
            self.lm,
            num_pages=num_pages,
            page_size=page_size,
            max_batch=max_batch,
        )
        cache_sh = None
        if self.plan is not None:
            cache_sh = self.plan.cache_sharding_tree(
                jax.eval_shape(make_cache), self.axes
            )
        # built in place on the engine's devices, like the params
        self.cache = jax.jit(make_cache, out_shardings=cache_sh)()
        self.page_tables = np.full(
            (max_batch, self.pages_per_seq), SCRATCH_PAGE, np.int32
        )
        # device-resident mirror of page_tables: rows only change on
        # join/evict, so we sync those rows in place instead of re-uploading
        # the whole host array every decode step
        self.page_tables_dev = jnp.asarray(self.page_tables)
        self.lengths = np.zeros(max_batch, np.int32)
        self.next_tokens = np.zeros(max_batch, np.int32)
        self._prefill = jax.jit(self.lm.prefill)
        self._decode = jax.jit(self.lm.decode_step_paged, donate_argnums=(3,))
        # chunk width is static (fixed jit shape); s0 is static too because
        # the flash q_offset feeds the compile-time causal mask — the jit
        # cache is keyed per distinct chunk start, a bounded set (multiples
        # of the chunk width offset by page-aligned shared-prefix starts)
        self._chunk = jax.jit(
            self.lm.prefill_chunk, static_argnames=("s0",), donate_argnums=(3,)
        )
        # every step timing rides the telemetry bus as a ServeStepEvent;
        # the deprecated ``telemetry`` property reconstructs legacy rows
        self.tracker = Tracker([MemorySink()])
        self._t_s = 0.0
        # opt-in hierarchical span tracing (DESIGN.md §14): spans share the
        # engine bus, so events()/to_jsonl carry them alongside serve_step
        # rows.  IDs are deterministic (seed-derived); timestamps come from
        # trace_clock (default wall clock — inject CountingClock for
        # byte-identical trace files across same-seed runs).
        self.spans: Optional[SpanTracer] = (
            SpanTracer(
                self.tracker,
                trace=("serve", self.cfg.name, seed, replica_id),
                replica=replica_id,
                clock=trace_clock,
            )
            if trace
            else None
        )
        self.scheduler.tracer = self.spans
        if self.plan is not None:
            self.page_tables_dev = self.plan.put_replicated(self.page_tables_dev)
            self._decode = self.plan.decode_jit(self.lm, self.params, self.cache)
            self._chunk = self.plan.prefill_chunk_jit(
                self.lm, self.params, self.cache
            )
        self.step_count = 0
        self._rid = 0
        self.replica_id = replica_id

    @staticmethod
    def config_for(arch: str, smoke: bool):
        return get_smoke_config(arch) if smoke else get_config(arch)

    @staticmethod
    def default_runtime(page_size: int = 16, **overrides) -> Runtime:
        """The serving Runtime (``overrides``: e.g. ``mesh``, ``paged_impl``).

        block_q = block_k = 16 pins the flash-attention blocking: the kernel
        clamps blocks to min(block, max(seq, 16)), so 16 is the one setting
        whose block grid never depends on prompt length.  That makes
        prefix-position activations — and therefore shared prefix pages —
        bitwise independent of what follows them, which is what lets prefix
        reuse skip rewriting shared pages (see write_prefill)."""
        return Runtime(remat="none", block_q=16, block_k=16, scan_chunk=32,
                       page_size=page_size, **overrides)

    def _sp(self, name: str, **attrs):
        """Span scope when tracing is on, else a free no-op context."""
        if self.spans is None:
            return nullcontext()
        return self.spans.span(name, step=self.step_count, **attrs)

    # ------------------------------------------------------------------
    def submit(
        self,
        prompt: np.ndarray,
        max_new_tokens: int,
        arrival_step: int = 0,
        frontend_embeds: Optional[np.ndarray] = None,
    ) -> Request:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n_front = 0 if frontend_embeds is None else self.cfg.n_frontend_tokens
        total = len(prompt) + n_front + max_new_tokens
        if total > self.max_seq:
            raise ValueError(
                f"prompt+generation needs {total} positions > max_seq={self.max_seq}"
            )
        req = Request(
            rid=self._rid,
            prompt=prompt,
            max_new_tokens=max_new_tokens,
            arrival_step=arrival_step,
            frontend_embeds=frontend_embeds,
        )
        if self.collect_logits:
            req.logits_trace = []
        self._rid += 1
        self.scheduler.submit(req)
        return req

    # ------------------------------------------------------------------
    def _admit(self, req: Request) -> None:
        """Prefill (or reuse a stored prefill) and seed the decode slot."""
        slot = req.slot
        n_front = 0 if req.frontend_embeds is None else self.cfg.n_frontend_tokens
        if req.prefill_skipped:
            with self._sp(
                "prefill",
                component="engine.prefill",
                rid=req.rid,
                tokens=len(req.prompt),
                skipped=True,
            ):
                logits = req.full_entry.last_logits
                self.cache = restore_state(
                    self.cache, req.full_entry.state, self.axes, slot
                )
            self._activate(req, logits, n_front)
        else:
            fe = (
                None
                if req.frontend_embeds is None
                else jnp.asarray(req.frontend_embeds)[None]
            )
            t0 = time.perf_counter()
            with self._sp(
                "prefill",
                component="engine.prefill",
                rid=req.rid,
                tokens=len(req.prompt),
            ):
                logits_dev, pre_cache = self._prefill(
                    self.params, jnp.asarray(req.prompt)[None], fe
                )
                logits_dev.block_until_ready()
            req.prefill_s = time.perf_counter() - t0
            self.cache = write_prefill(
                self.cache,
                pre_cache,
                self.axes,
                slot=slot,
                page_ids=req.page_ids,
                page_size=self.page_size,
                skip_pages=req.n_shared_pages,
            )
            self._finish_prefill(req, logits_dev, (0,), n_front)

    def _finish_prefill(self, req: Request, logits_dev, index, n_front: int) -> None:
        """Fetch the prompt's last-token logits (``logits_dev[index]``),
        register its pages with the prefix cache and arm the slot."""
        with self._sp("activate", component="engine.activate", rid=req.rid):
            logits = np.asarray(logits_dev[index])
            if self.prefix is not None and req.frontend_embeds is None:
                n_prompt_pages = -(-len(req.prompt) // self.page_size)
                self.prefix.register(
                    req.prompt, req.page_ids[:n_prompt_pages], self.pool
                )
                self.prefix.register_full(
                    req.prompt,
                    req.page_ids[: len(req.prompt) // self.page_size],
                    logits,
                    snapshot_state(self.cache, self.axes, req.slot),
                    self.pool,
                )
            self._activate(req, logits, n_front)

    def _activate(self, req: Request, logits: np.ndarray, n_front: int) -> None:
        """Seed the first token from prefill logits and arm the decode slot."""
        slot = req.slot
        tok = int(np.argmax(logits))
        req.generated.append(tok)
        if req.logits_trace is not None:
            req.logits_trace.append(np.asarray(logits, np.float32).copy())
        req.state = RequestState.RUNNING
        req.first_token_step = self.step_count
        self.lengths[slot] = len(req.prompt) + n_front
        row = np.full(self.pages_per_seq, SCRATCH_PAGE, np.int32)
        row[: len(req.page_ids)] = req.page_ids
        self.page_tables[slot] = row
        self.page_tables_dev = self.page_tables_dev.at[slot].set(jnp.asarray(row))
        self.next_tokens[slot] = tok

    # ------------------------------------------------------------------
    def _use_chunked(self, req: Request) -> bool:
        """Chunked prefill applies when there is new prompt to stream in:
        skipped prefills are free, frontend embeds use the legacy path, and
        an all-shared prompt head falls back to the (cheap) full prefill so
        the last-token logits exist to seed decode."""
        return (
            self.prefill_chunk is not None
            and req.frontend_embeds is None
            and not req.prefill_skipped
            and req.n_shared_pages * self.page_size < len(req.prompt)
        )

    def _prefill_chunk_step(self, req: Request, n_tokens: int) -> None:
        """Run one chunk of ``req``'s prompt through the paged stack.  While
        PREFILLING the slot's host page-table row stays at SCRATCH (the slot
        is invisible to decode/verify); the real row is passed straight to
        the chunk jit.  The final chunk registers prefix pages and activates
        the slot."""
        slot = req.slot
        s0 = req.prefill_pos
        c = self.prefill_chunk
        chunk = np.zeros(c, np.int32)
        chunk[:n_tokens] = req.prompt[s0: s0 + n_tokens]
        row = np.full(self.pages_per_seq, SCRATCH_PAGE, np.int32)
        row[: len(req.page_ids)] = req.page_ids
        t0 = time.perf_counter()
        with self._sp(
            "prefill_chunk",
            component="engine.prefill_chunk",
            rid=req.rid,
            tokens=n_tokens,
            s0=s0,
        ) as sp:
            logits_dev, self.cache, *counts_dev = self._chunk(
                self.params,
                jnp.asarray(chunk)[None],
                jnp.int32(n_tokens),
                self.cache,
                jnp.asarray(row)[None],
                s0=s0,
            )
            logits_dev.block_until_ready()
            _, counts = self._fetch(None, counts_dev, sp)
        dt = time.perf_counter() - t0
        req.prefill_s += dt
        req.prefill_pos += n_tokens
        self._emit("prefill", batch=0, step_s=dt, prefill_tokens=n_tokens,
                   **counts)
        if req.prefill_pos >= len(req.prompt):
            self._finish_prefill(req, logits_dev, (0, n_tokens - 1), 0)

    def _fetch(self, logits_dev, counts_dev, span):
        """(logits on the host, MoE counts): with tracing on, a step's
        ``[routed_rows, expert_rows]`` (``counts_dev``, the step's extra
        output; empty without MoE layers) come to the host with the logits
        and are set on ``span``; with tracing off nothing more is fetched
        and the counts are ``{}``."""
        if span is None or not counts_dev:
            logits = None if logits_dev is None else np.asarray(logits_dev)
            return logits, {}
        logits, (routed, rows) = jax.device_get((logits_dev, counts_dev[0]))
        counts = {"routed_rows": int(routed), "expert_rows": int(rows)}
        span.set(**counts)
        return logits, counts

    def _release_slot(self, slot: int) -> None:
        self.lengths[slot] = 0
        self.next_tokens[slot] = 0
        self.page_tables[slot] = SCRATCH_PAGE
        self.page_tables_dev = self.page_tables_dev.at[slot].set(SCRATCH_PAGE)

    def _retire(self, reqs: List[Request]) -> None:
        """Evict the finished requests: their slots and pages go free."""
        if not reqs:
            return
        with self._sp("retire", component="engine.retire", n=len(reqs)):
            for req in reqs:
                slot = req.slot
                self.scheduler.finish(req, self.step_count)
                self._release_slot(slot)

    # ------------------------------------------------------------------
    def step(self) -> int:
        """One unified engine step: admit arrived requests, advance chunked
        prefill within its token budget, then run one batched decode (or
        draft-verify) step and retire finished requests.  Returns the number
        of requests that contributed decode tokens."""
        with self._sp("step", component="engine.step"):
            return self._step_inner()

    def _step_inner(self) -> int:
        with self._sp("schedule", component="engine.schedule"):
            whole = []  # admitted requests prefilled in one call
            for req in self.scheduler.admit_ready(self.step_count):
                if self._use_chunked(req):
                    req.state = RequestState.PREFILLING
                    req.prefill_pos = req.n_shared_pages * self.page_size
                else:
                    whole.append(req)
            chunks = self.scheduler.plan_prefill()
        for req in whole:
            self._admit(req)
            if req.done:  # max_new_tokens == 1: prefill already finished
                self._retire([req])
        for req, take in chunks:
            self._prefill_chunk_step(req, take)
            if req.state is RequestState.RUNNING and req.done:
                self._retire([req])
        decoding = self.scheduler.decoding
        if not decoding:
            self.step_count += 1
            return 0
        drafts = self._propose_drafts(decoding) if self.speculate else None
        if drafts is not None:
            n = self._verify_step(decoding, drafts)
            self.step_count += 1
            return n
        t0 = time.perf_counter()
        with self._sp(
            "decode", component="engine.decode", batch=len(decoding)
        ) as sp:
            with self._sp("launch", component="engine.decode.launch"):
                logits_dev, self.cache, *counts_dev = self._decode(
                    self.params,
                    jnp.asarray(self.next_tokens),
                    jnp.asarray(self.lengths),
                    self.cache,
                    self.page_tables_dev,
                )
            with self._sp("wait", component="engine.decode.wait"):
                logits_dev.block_until_ready()
            with self._sp("fetch", component="engine.decode.fetch"):
                logits_np, counts = self._fetch(logits_dev, counts_dev, sp)
        dt = time.perf_counter() - t0
        self._emit(
            "decode",
            batch=len(decoding),
            step_s=dt,
            committed=len(decoding),
            **counts,
        )
        with self._sp("sample", component="engine.sample", batch=len(decoding)):
            for req in decoding:
                slot = req.slot
                tok = int(np.argmax(logits_np[slot]))
                req.generated.append(tok)
                if req.logits_trace is not None:
                    req.logits_trace.append(
                        logits_np[slot].astype(np.float32).copy()
                    )
                self.lengths[slot] += 1
                self.next_tokens[slot] = tok
        self._retire([req for req in decoding if req.done])
        self.step_count += 1
        return len(decoding)

    # ------------------------------------------------------------------
    def _propose_drafts(self, decoding) -> Optional[Dict[int, np.ndarray]]:
        """Draft tokens per slot (``None`` means run the plain decode step).
        Draft count is capped at ``remaining - 1`` so no speculative write
        lands past the position the baseline's final decode step would use.

        A verify step runs ``max_batch * (k+1)`` rows where plain decode
        runs ``max_batch`` — roughly a 2x wall premium at serving shapes —
        so sparse drafts lose even when they are right.  The step is only
        worth it when drafting is dense (every slot deep in a predictable
        stretch, e.g. looping or prompt-copying output), so the gate
        requires two full-depth drafts' worth of tokens per active slot
        before paying for verification; anything less decodes normally and
        costs speculation nothing."""
        drafts: Dict[int, np.ndarray] = {}
        total = 0
        for req in decoding:
            remaining = req.max_new_tokens - len(req.generated)
            cap = min(self.speculate, remaining - 1)
            if cap > 0:
                ctx = np.concatenate(
                    [req.prompt, np.asarray(req.generated, np.int32)]
                )
                d = self.proposer.propose(ctx, cap, slot=req.slot)
            else:
                d = np.empty(0, np.int32)
            drafts[req.slot] = d
            total += len(d)
        gate = len(decoding) * min(self.speculate, 2)
        return drafts if total >= max(gate, 1) else None

    def _verify_step(self, decoding, drafts: Dict[int, np.ndarray]) -> int:
        """One batched draft-verify step: fold each slot to ``k+1`` rows of
        the regular paged decode step (row t = pending token if t=0 else
        draft t, at length L+t, sharing the slot's page-table row), then
        commit the longest accepted prefix per slot.  Row t's logits are the
        target model's next-token distribution after consuming the pending
        token and drafts 1..t — bitwise the sequential decode's logits
        whenever those drafts match what it would have committed, which is
        exactly the accept condition (DESIGN.md §11).  Padded rows get
        length 0 and an all-scratch page-table row so they can neither read
        nor clobber live pages."""
        t_rows = self.speculate + 1
        n_rows = self.max_batch * t_rows
        toks = np.zeros(n_rows, np.int32)
        lens = np.zeros(n_rows, np.int32)
        pts = np.full((n_rows, self.pages_per_seq), SCRATCH_PAGE, np.int32)
        for req in decoding:
            s = req.slot
            d = drafts[s]
            base = s * t_rows
            toks[base] = self.next_tokens[s]
            toks[base + 1: base + 1 + len(d)] = d
            lens[base: base + 1 + len(d)] = self.lengths[s] + np.arange(
                len(d) + 1
            )
            pts[base: base + 1 + len(d)] = self.page_tables[s]
        t0 = time.perf_counter()
        with self._sp(
            "verify",
            component="engine.verify",
            batch=len(decoding),
            rows=n_rows,
        ):
            logits_dev, self.cache, *_ = self._decode(
                self.params,
                jnp.asarray(toks),
                jnp.asarray(lens),
                self.cache,
                jnp.asarray(pts),
            )
            logits_np = np.asarray(logits_dev)
        dt = time.perf_counter() - t0
        total_committed = 0
        total_drafted = 0
        for req in decoding:
            s = req.slot
            d = drafts[s]
            rows = logits_np[s * t_rows: (s + 1) * t_rows]
            committed = [int(np.argmax(rows[0]))]
            for i in range(len(d)):
                if int(d[i]) != committed[i]:
                    break
                committed.append(int(np.argmax(rows[i + 1])))
            self.proposer.record(len(d), len(committed) - 1)
            for i, tok in enumerate(committed):
                req.generated.append(tok)
                if req.logits_trace is not None:
                    req.logits_trace.append(rows[i].astype(np.float32).copy())
            self.lengths[s] += len(committed)
            self.next_tokens[s] = committed[-1]
            total_committed += len(committed)
            total_drafted += len(d)
            if req.done:
                slot = req.slot
                self.scheduler.finish(req, self.step_count)
                self._release_slot(slot)
        self._emit(
            "verify",
            batch=len(decoding),
            step_s=dt,
            committed=total_committed,
            drafted=total_drafted,
        )
        return len(decoding)

    def run(self, max_steps: int = 100_000) -> Dict:
        """Drive steps until every submitted request has finished."""
        while not self.scheduler.drained:
            if self.step_count >= max_steps:
                raise RuntimeError(f"trace did not drain in {max_steps} steps")
            self.step()
        return self.stats()

    # ------------------------------------------------------------------
    def _emit(
        self,
        op: str,
        *,
        batch: int,
        step_s: float,
        committed: int = 0,
        drafted: int = 0,
        prefill_tokens: int = 0,
        routed_rows: int = 0,
        expert_rows: int = 0,
    ) -> None:
        self._t_s += step_s
        self.tracker.emit(
            ServeStepEvent(
                step=self.step_count,
                step_s=step_s,
                op=op,
                batch=batch,
                committed=committed,
                drafted=drafted,
                prefill_tokens=prefill_tokens,
                t_s=self._t_s,
                replica=self.replica_id,
                routed_rows=routed_rows,
                expert_rows=expert_rows,
            )
        )

    def events(self, kind: Optional[str] = None) -> List[Event]:
        """Typed events on the engine's bus (``serve_step`` rows)."""
        return self.tracker.events(kind)

    def to_jsonl(self, path) -> int:
        """Dump the engine's event stream as JSONL."""
        return self.tracker.to_jsonl(path)

    @property
    def telemetry(self) -> List[Dict]:
        """Deprecated: legacy row dicts; use ``events()`` instead."""
        warn_deprecated("ServeEngine.telemetry", 'ServeEngine.events("serve_step")')
        return [e.to_legacy() for e in self.tracker.events("serve_step")]

    # ------------------------------------------------------------------
    def stats(self) -> Dict:
        evs = self.events("serve_step")
        steps = [e for e in evs if e.batch > 0]
        tok = sum(e.committed for e in steps)
        busy = sum(e.step_s for e in steps)
        batch_tok = sum(e.batch for e in steps)
        out: Dict = {
            "requests_finished": len(self.scheduler.finished),
            "decode_steps": len(steps),
            "decode_tokens": tok,
            "decode_tok_per_s": tok / busy if busy else 0.0,
            "mean_batch": batch_tok / len(steps) if steps else 0.0,
            "pages_in_use": self.pool.pages_in_use,
            "free_pages": self.pool.free_pages,
        }
        if self.prefix is not None:
            out["prefix_hits"] = self.prefix.hits
            out["prefix_pages_shared"] = self.prefix.pages_shared
            out["prefills_skipped"] = self.prefix.prefills_skipped
        if self.prefill_chunk is not None:
            chunks = [e for e in evs if e.op == "prefill"]
            out["prefill_chunks"] = len(chunks)
            out["prefill_chunk_tokens"] = sum(e.prefill_tokens for e in chunks)
        if self.proposer is not None:
            out["draft_proposed"] = self.proposer.proposed_tokens
            out["draft_accepted"] = self.proposer.accepted_tokens
            out["spec_accept_rate"] = self.proposer.accept_rate
        joins = [
            r.first_token_step - r.arrival_step
            for r in self.scheduler.finished
            if r.first_token_step >= 0
        ]
        if joins:
            out["join_to_first_token_p50"] = float(np.percentile(joins, 50))
            out["join_to_first_token_p99"] = float(np.percentile(joins, 99))
        return out
