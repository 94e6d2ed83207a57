"""Serving CLI — thin front end over the ``repro.serve`` subsystem.

Continuous batching (paged KV cache, join-on-arrival, prefix reuse,
Hemingway capacity planning):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --smoke \
      --continuous

runs a mixed-length 8-request trace with staggered arrivals and shared
prompt heads, checks prefix-reuse logits against a cold prefill bit-for-bit,
and prints the fitted f(b) step model plus a capacity plan (what replica
count m and max-batch hit a p50 target at a given QPS).

Multi-replica routed serving (DESIGN.md §13):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --smoke \
      --continuous --router --replicas 2

replays the same trace through a prefix-affinity router over N replicas
(``--replicas 0`` asks the fitted capacity planner for its min-replicas
answer) and asserts every request's token stream is bit-identical to the
single-engine reference.  ``--tp K`` additionally runs each replica
tensor-parallel over K forced-host devices.

Static batch (the original demo, now also served by the engine):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --smoke \
      --batch 4 --prompt-len 16 --gen 16
"""
from __future__ import annotations

import os
import sys

# --tp K forces K host devices; jax locks the device count at first
# initialization, so this must run before ANY jax-importing import below
# (same contract as launch/dryrun.py).
if "--tp" in sys.argv[1:]:
    _k = int(sys.argv[sys.argv.index("--tp") + 1])
    if _k > 1 and "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={_k}").strip()

import argparse
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.serve import CapacityPlanner, Router, ServeEngine


class Server:
    """Batch-synchronous facade kept for tests/back-compat; every request is
    admitted at step 0 and decoded by the continuous engine.  Passing a mesh
    (and optionally Rules) runs the sharded data plane (serve/sharding.py)."""

    def __init__(self, arch: str, smoke: bool = True, max_seq: int = 128,
                 mesh=None, rules=None, seed: int = 0, page_size: int = 16):
        self.arch = arch
        self.smoke = smoke
        self.max_seq = max_seq
        self.seed = seed
        self.page_size = page_size
        self.rt = None
        if mesh is not None or rules is not None:
            self.rt = ServeEngine.default_runtime(page_size, mesh=mesh,
                                                  rules=rules)
        self._engine: Optional[ServeEngine] = None
        self.cfg = ServeEngine.config_for(arch, smoke)

    def _make_engine(self, batch: int) -> ServeEngine:
        if self._engine is None or self._engine.max_batch != batch:
            self._engine = ServeEngine(
                self.arch, smoke=self.smoke, max_batch=batch,
                page_size=self.page_size, max_seq=self.max_seq,
                seed=self.seed, rt=self.rt)
        return self._engine

    def generate(self, prompts: np.ndarray, gen_tokens: int,
                 frontend_embeds: Optional[np.ndarray] = None,
                 greedy: bool = True) -> Dict:
        """prompts: (B, P) int32. Returns generated tokens + timing stats."""
        assert greedy, "only greedy decoding is supported"
        b, _ = prompts.shape
        eng = self._make_engine(b)
        # engine may be reused across calls
        n_before = len(eng.events("serve_step"))
        reqs = []
        for i in range(b):
            fe = None if frontend_embeds is None else frontend_embeds[i]
            reqs.append(eng.submit(np.asarray(prompts[i], np.int32),
                                   gen_tokens, frontend_embeds=fe))
        eng.run()
        tokens = np.stack([np.asarray(r.generated, np.int32) for r in reqs])
        this_call = [e for e in eng.events("serve_step")[n_before:]
                     if e.batch > 0]
        t_decode = sum(e.step_s for e in this_call)
        n_tok = sum(e.batch for e in this_call)
        return {
            "tokens": tokens,
            "prefill_s": sum(r.prefill_s for r in reqs),
            "decode_s": t_decode,
            "decode_tok_per_s": n_tok / t_decode if t_decode else 0.0,
        }


# One trace request: (prompt, gen_tokens, arrival_step, frontend_embeds).
TraceSpec = Tuple[np.ndarray, int, int, Optional[np.ndarray]]


def _mixed_trace_specs(cfg, page_size: int, n_requests: int,
                       seed: int) -> List[TraceSpec]:
    """Mixed prompt lengths, bursty arrivals, one shared prompt head —
    generated independently of any engine so the same trace can be replayed
    through a single engine and a routed fleet.  The RNG draw order is
    load-bearing: it pins the traces existing goldens/smoke output use."""
    rng = np.random.RandomState(seed)
    ps = page_size
    shared_head = rng.randint(0, cfg.vocab_size, 2 * ps).astype(np.int32)
    specs: List[TraceSpec] = []
    for i in range(n_requests):
        if i % 3 == 0:  # every third request shares the prompt head
            tail = rng.randint(0, cfg.vocab_size,
                               3 + rng.randint(0, ps)).astype(np.int32)
            prompt = np.concatenate([shared_head, tail])
        else:
            plen = int(rng.choice([7, 12, 21, 30]))
            prompt = rng.randint(0, cfg.vocab_size, plen).astype(np.int32)
        gen = int(rng.choice([4, 6, 8]))
        arrival = (i // 2) * 2  # bursty: pairs arrive together
        fe = None
        if cfg.n_frontend_tokens:
            fe = (rng.randn(cfg.n_frontend_tokens, cfg.d_model)
                  * 0.02).astype(np.float32)
        specs.append((prompt, gen, arrival, fe))
    return specs


def _submit_specs(eng: ServeEngine, specs: List[TraceSpec]):
    return [eng.submit(prompt, gen, arrival_step=arrival, frontend_embeds=fe)
            for prompt, gen, arrival, fe in specs]


# generation length of each prefix-reuse probe request
_PROBE_GEN = 4


def _prefix_probe_prompts(cfg, page_size: int,
                          seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """Two prompts sharing a two-page head, for ``_verify_prefix_reuse``."""
    rng = np.random.RandomState(seed + 1)
    head = rng.randint(0, cfg.vocab_size, 2 * page_size).astype(np.int32)
    pA = np.concatenate([head, rng.randint(0, cfg.vocab_size, 5)
                         .astype(np.int32)])
    pB = np.concatenate([head, rng.randint(0, cfg.vocab_size, 9)
                         .astype(np.int32)])
    return pA, pB


def _trace_max_seq(cfg, specs: List[TraceSpec], probes, page_size: int) -> int:
    """Cache positions the longest request served needs (trace requests
    and prefix-reuse probes), rounded up to whole pages: the engines'
    ``max_seq``, from which their page tables and pools are sized."""
    need = [len(prompt) + gen + (0 if fe is None else cfg.n_frontend_tokens)
            for prompt, gen, _, fe in specs]
    need += [len(p) + _PROBE_GEN for p in probes]
    return -(-max(need) // page_size) * page_size


def _verify_prefix_reuse(arch: str, smoke: bool, eng: ServeEngine,
                         probes) -> bool:
    """Serve one prefix-sharing prompt on the warm engine and the same
    prompt cold (a fresh engine over the same weights, configured alike);
    logits must match bit-for-bit.  The cold engine prefills and decodes
    through the same programs as the warm one (chunked or not, speculative
    or not), so the check isolates the reuse of shared pages: on a TPU a
    chunked and a monolithic prefill are different XLA programs, whose
    rounding is not bitwise alike."""
    pA, pB = probes
    eng.collect_logits = True
    eng.submit(pA, _PROBE_GEN)
    eng.run()
    rB = eng.submit(pB, _PROBE_GEN)
    eng.run()
    cold = ServeEngine(arch, smoke=smoke,
                       max_batch=eng.max_batch, page_size=eng.page_size,
                       max_seq=eng.max_seq, seed=eng.seed, params=eng.params,
                       rt=eng.rt, prefill_chunk=eng.prefill_chunk,
                       speculate=eng.speculate, collect_logits=True)
    rB_cold = cold.submit(pB, _PROBE_GEN)
    cold.run()
    shared = rB.n_shared_pages
    exact = all(np.array_equal(a, b)
                for a, b in zip(rB.logits_trace, rB_cold.logits_trace))
    print(f"prefix reuse: shared_pages={shared} "
          f"bit_identical={'yes' if exact else 'NO'}")
    return shared > 0 and exact


def _resolve_prefill_chunk(value: Optional[int], smoke: bool) -> Optional[int]:
    """``--prefill-chunk -1`` -> the autotuned chunk size for the matching
    sweep preset; falls back to the built-in default on a cache miss."""
    if value is None or value >= 0:
        return value
    import jax.numpy as jnp

    from repro.kernels.flash_decode.ops import DEFAULT_PREFILL_CHUNK
    from repro.kernels.tune import SWEEP_SHAPES, lookup

    preset = "smoke" if smoke else "full"
    cfg = lookup("prefill_chunk", SWEEP_SHAPES[preset]["prefill_chunk"],
                 jnp.float32)
    chunk = int(cfg["chunk"]) if cfg else DEFAULT_PREFILL_CHUNK
    print(f"prefill chunk: auto -> {chunk} "
          f"({'tuned' if cfg else 'untuned default'})")
    return chunk


def _trace_clock_factory(args):
    """Per-engine trace clock: fresh CountingClock for ``steps`` (fully
    deterministic span values -> byte-identical trace files across
    same-seed runs), ``None`` (wall clock) otherwise."""
    if args.trace and args.trace_clock == "steps":
        from repro.telemetry.trace import CountingClock

        return lambda: CountingClock()
    return lambda: None


def _export_trace(args, events, planner, busy_s: float, n_layers: int) -> None:
    """Write the Perfetto trace + attribution report; exit 1 on failure.

    Reconciliation compares the engine-op span components against the
    engine's own ``serve_step`` wall time — the same scopes timed by two
    perf_counter pairs, so the acceptance bound (5%) is generous.  Under
    ``--trace-clock steps`` span values are synthetic ticks and the wall
    reconciliation is skipped (byte-identity is the point of that mode)."""
    from repro.telemetry.trace import (
        attribute,
        format_attribution,
        load_perfetto,
        validate_perfetto,
        write_perfetto,
    )

    fitted = None
    try:
        planner.step_time(1)
        fitted = planner
    except Exception:
        pass
    n = write_perfetto(args.trace, events)
    errs = validate_perfetto(load_perfetto(args.trace))
    if errs:
        print(f"FAIL: trace schema: {errs[:5]}")
        sys.exit(1)
    print(f"trace: {n} spans -> {args.trace} (Perfetto/chrome://tracing)")
    attr = attribute(events, planner=fitted, n_layers=n_layers)
    print(format_attribution(attr))
    # serve_step rows time exactly decode, verify, and *chunked* prefill;
    # monolithic admission prefill (engine.prefill) is span-only (the
    # engine books it on the request, not the step stream), so it stays
    # out of the wall reconciliation set
    engine_ops = ("engine.decode", "engine.verify", "engine.prefill_chunk")
    span_busy = sum(r.measured_s for r in attr.rows
                    if r.component in engine_ops)
    if args.trace_clock == "steps":
        print("trace: deterministic step clock (wall reconciliation n/a)")
        return
    if busy_s > 0:
        rel = abs(span_busy - busy_s) / busy_s
        print(f"trace: span/engine wall reconciliation "
              f"{span_busy:.3f}s vs {busy_s:.3f}s ({rel:.2%})")
        if rel > 0.05:
            print("FAIL: trace spans do not reconcile with engine wall time")
            sys.exit(1)


def _run_router(args, eng: ServeEngine, specs: List[TraceSpec], reference,
                n_replicas: int, prefill_chunk: Optional[int]) -> "Router":
    """Replay the reference trace through a prefix-affinity router over
    ``n_replicas`` engines and assert bit-identical per-request outputs.

    Every replica serves ``eng``'s weights.  With ``--tp K`` each runs on
    one K-device mesh; otherwise, where several devices exist, replica i is
    pinned to device i (mod the device count) by a one-device mesh."""
    import jax

    from repro.launch.mesh import make_debug_mesh

    devices = jax.devices()
    mesh = None
    if args.tp > 1:
        mesh = make_debug_mesh(1, args.tp)
        print(f"tensor parallel: {args.tp}-way over mesh "
              f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")

    def placement(i: int):
        if mesh is not None or len(devices) == 1:
            return mesh
        return make_debug_mesh(1, 1, devices=[devices[max(i, 0) % len(devices)]])

    clock = _trace_clock_factory(args)

    def make_engine(i: int) -> ServeEngine:
        rt = ServeEngine.default_runtime(args.page_size,
                                         paged_impl=args.paged_impl,
                                         mesh=placement(i))
        return ServeEngine(
            args.arch, smoke=args.smoke, max_batch=args.max_batch,
            page_size=args.page_size, max_seq=eng.max_seq,
            seed=args.seed, params=eng.params, rt=rt,
            prefill_chunk=prefill_chunk, speculate=args.speculate,
            replica_id=i, trace=bool(args.trace), trace_clock=clock())

    if args.tp > 1:
        # bit-identity is a same-placement guarantee: TP psums reduce in a
        # different order than the unsharded engine, so at K > 1 the routed
        # fleet is compared against a single engine on the SAME mesh (the
        # unsharded reference agrees to float tolerance, not bitwise)
        ref = make_engine(-1)
        _submit_specs(ref, specs)
        ref.run()
        reference = ref.scheduler.finished
        reference.sort(key=lambda r: r.rid)

    engines = [make_engine(i) for i in range(n_replicas)]
    router = Router(engines, spill_slack=args.spill_slack,
                    trace=bool(args.trace), trace_clock=clock())
    routed = [router.submit(prompt, gen, arrival_step=arrival,
                            frontend_embeds=fe)
              for prompt, gen, arrival, fe in specs]
    if args.migrate_at is not None:
        from repro.serve.migrate import migrate_replica

        migrated = False
        while not router.drained:
            if router.step_count >= 100_000:
                raise RuntimeError("trace did not drain in 100000 steps")
            if router.step_count == args.migrate_at:
                info = migrate_replica(
                    router, args.migrate_replica,
                    lambda: make_engine(args.migrate_replica))
                migrated = True
                print(f"migration: replica {info['replica']} handed off at "
                      f"step {args.migrate_at} — {info['in_flight']} "
                      f"requests in flight, {info['pages_in_use']} pages, "
                      f"{info['nbytes'] / 1e6:.2f} MB cache in "
                      f"{info['wall_s'] * 1e3:.0f} ms")
            router.step()
        if not migrated:
            print(f"migration: trace drained before step {args.migrate_at} "
                  f"(no handoff performed)")
        rstats = router.stats()
    else:
        rstats = router.run()
    print(f"router: {rstats['dispatched']} requests over "
          f"{n_replicas} replicas {rstats['dispatch_per_replica']}, "
          f"affinity hit rate {rstats['affinity_hit_rate']:.2f} "
          f"({rstats['affinity_hits']} hits, {rstats['spills']} spills)")

    identical = all(rr.generated == ref.generated
                    for rr, ref in zip(routed, reference))
    print(f"routed fleet vs single engine: "
          f"bit_identical={'yes' if identical else 'NO'}")

    planner = CapacityPlanner()
    planner.ingest(router.all_events())
    per = planner.replica_stats()
    for idx, s in per.items():
        print(f"  replica {idx}: {int(s['dispatches'])} dispatched, "
              f"{int(s['affinity_hits'])} affinity hits, "
              f"{int(s['decode_tokens'])} tokens @ {s['tok_per_s']:.1f} tok/s")
    print(f"measured effective replicas: "
          f"{planner.measured_effective_replicas():.2f}/{n_replicas}")

    if args.router_log:
        n = router.to_jsonl(args.router_log)
        print(f"router log: {n} events -> {args.router_log}")
    if not identical:
        print("FAIL: routed outputs diverge from the single-engine reference")
        sys.exit(1)
    return router


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced config (default; --no-smoke serves the "
                         "full architecture)")
    ap.add_argument("--continuous", action="store_true",
                    help="mixed-length trace with join-on-arrival + "
                         "prefix-reuse verification + capacity plan")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    metavar="TOKENS",
                    help="chunked prefill: per-step prompt-token budget "
                         "shared with the decode batch (-1 picks the "
                         "autotuned chunk size; default off)")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="speculative decode: draft up to K tokens per "
                         "sequence per step from an n-gram/prefix-cache "
                         "proposer, verified in one batched target step "
                         "(default 0 = off)")
    ap.add_argument("--paged-impl", default=None,
                    choices=["stream", "pallas", "gather"],
                    help="paged decode implementation (default: the Pallas "
                         "kernel on a TPU with one device per replica, "
                         "stream otherwise; stream and gather are "
                         "bit-identical, gather is the legacy oracle)")
    ap.add_argument("--tune-cache", default=None, metavar="PATH",
                    help="seed the capacity planner with measured "
                         "paged-decode kernel timings from this autotuner "
                         "config cache before fitting")
    ap.add_argument("--router", action="store_true",
                    help="replay the trace through a prefix-affinity router "
                         "over N replicas and assert bit-identical outputs "
                         "(implies --continuous)")
    ap.add_argument("--replicas", type=int, default=0, metavar="N",
                    help="replica count for --router (0 = the fitted "
                         "capacity planner's min-replicas answer)")
    ap.add_argument("--spill-slack", type=int, default=512, metavar="TOKENS",
                    help="router overflow spill: an affinity winner more "
                         "than this many pending tokens above the fleet "
                         "minimum forfeits the request")
    ap.add_argument("--migrate-at", type=int, default=None, metavar="STEP",
                    help="live migration drill: at router step STEP, hand "
                         "one replica off to a freshly built engine "
                         "(serve/migrate.py) and keep serving — the "
                         "bit-identity check then also proves migrated "
                         "streams match the unmigrated control (implies "
                         "--router)")
    ap.add_argument("--migrate-replica", type=int, default=0, metavar="R",
                    help="which replica --migrate-at hands off (default 0)")
    ap.add_argument("--router-log", default=None, metavar="PATH",
                    help="dump the combined router + replica event stream "
                         "as JSONL")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="hierarchical span tracing: write a Perfetto/"
                         "chrome://tracing JSON span tree and print the "
                         "per-component predicted-vs-measured attribution "
                         "report (implies --continuous)")
    ap.add_argument("--trace-clock", default="wall",
                    choices=["wall", "steps"],
                    help="span timestamp source: wall (measured; reconciled "
                         "against engine step timings) or steps "
                         "(deterministic tick clock; same-seed runs emit "
                         "byte-identical trace files)")
    ap.add_argument("--tp", type=int, default=1, metavar="K",
                    help="tensor-parallel world size per replica (forces K "
                         "host devices; must be first jax initialization)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.migrate_at is not None:
        args.router = True
    if args.router or args.trace:
        args.continuous = True

    if not args.continuous:
        server = Server(args.arch, smoke=args.smoke,
                        max_seq=args.prompt_len + args.gen + 8,
                        page_size=args.page_size)
        rng = np.random.RandomState(args.seed)
        prompts = rng.randint(0, server.cfg.vocab_size,
                              (args.batch, args.prompt_len)).astype(np.int32)
        fe = None
        if server.cfg.n_frontend_tokens:
            fe = rng.randn(args.batch, server.cfg.n_frontend_tokens,
                           server.cfg.d_model).astype(np.float32) * 0.02
        res = server.generate(prompts, args.gen, fe)
        print(f"generated {res['tokens'].shape} tokens; "
              f"prefill {res['prefill_s']*1e3:.0f} ms, "
              f"decode {res['decode_tok_per_s']:.1f} tok/s")
        return

    prefill_chunk = _resolve_prefill_chunk(args.prefill_chunk, args.smoke)
    cfg = ServeEngine.config_for(args.arch, args.smoke)
    specs = _mixed_trace_specs(cfg, args.page_size, args.requests, args.seed)
    probes = _prefix_probe_prompts(cfg, args.page_size, args.seed)
    eng = ServeEngine(args.arch, smoke=args.smoke, max_batch=args.max_batch,
                      page_size=args.page_size,
                      max_seq=_trace_max_seq(cfg, specs, probes,
                                             args.page_size),
                      seed=args.seed, paged_impl=args.paged_impl,
                      prefill_chunk=prefill_chunk, speculate=args.speculate,
                      trace=bool(args.trace),
                      trace_clock=_trace_clock_factory(args)())
    print(f"engine: {eng.cfg.name} max_seq={eng.max_seq} "
          f"pages={eng.pool.num_pages} paged_impl={eng.rt.paged_impl}")
    reqs = _submit_specs(eng, specs)
    stats = eng.run()
    done = [r for r in reqs if r.finished_step >= 0]
    print(f"served {len(done)}/{len(reqs)} requests in {eng.step_count} steps "
          f"(mean batch {stats['mean_batch']:.2f}, "
          f"{stats['decode_tok_per_s']:.1f} tok/s, "
          f"prefix hits {stats.get('prefix_hits', 0)})")
    joins = sum(1 for r in reqs if r.admitted_step > 0)
    print(f"join-on-arrival: {joins} requests joined a running batch")
    if "join_to_first_token_p50" in stats:
        print(f"join-to-first-token: p50 {stats['join_to_first_token_p50']:.1f}"
              f" p99 {stats['join_to_first_token_p99']:.1f} steps")

    if prefill_chunk is not None or args.speculate:
        if prefill_chunk is not None:
            print(f"chunked prefill: {stats.get('prefill_chunks', 0)} chunk "
                  f"steps / {stats.get('prefill_chunk_tokens', 0)} prompt "
                  f"tokens at budget {prefill_chunk}")
        if args.speculate:
            print(f"speculation: accept rate "
                  f"{stats.get('spec_accept_rate', 0.0):.2f} "
                  f"({stats.get('draft_accepted', 0)}/"
                  f"{stats.get('draft_proposed', 0)} drafted tokens)")
        base = ServeEngine(args.arch, smoke=args.smoke,
                           max_batch=args.max_batch,
                           page_size=args.page_size, max_seq=eng.max_seq,
                           seed=args.seed, params=eng.params, rt=eng.rt)
        base_reqs = _submit_specs(base, specs)
        base.run()
        identical = all(r.generated == b.generated
                        for r, b in zip(reqs, base_reqs))
        print(f"chunked+speculative vs one-token baseline: "
              f"bit_identical={'yes' if identical else 'NO'}")
        if not identical:
            print("FAIL: chunked/speculative outputs diverge from baseline")
            sys.exit(1)

    planner = CapacityPlanner()
    tune_evs: List = []
    if args.tune_cache:
        from repro.kernels.tune import ConfigCache, tune_events

        n_layers = eng.cfg.n_layers
        tune_evs = list(tune_events(ConfigCache(args.tune_cache)))
        n = planner.ingest(tune_evs, n_layers=n_layers)
        print(f"capacity plan: seeded with {n} measured kernel row(s) "
              f"from {args.tune_cache} (x{n_layers} layers)")
    planner.ingest(eng.events("serve_step"))
    plan = None
    try:
        planner.fit()
    except ValueError as e:
        print(f"capacity plan: insufficient telemetry ({e})")
    else:
        t1, t8 = planner.step_time(1), planner.step_time(8)
        print(f"f(b) step model: t(1)={t1*1e3:.1f} ms  t(8)={t8*1e3:.1f} ms  "
              f"coeffs={planner.step_model.coefficients()}")
        plan = planner.plan(target_p50_s=max(10 * t8 * 8, 1e-3), qps=2.0,
                            gen_tokens=8, batch_grid=[1, 2, 4, 8],
                            m_grid=[1, 2, 4, 8, 16])
        if plan:
            print(f"capacity plan: {plan.algorithm} on m={plan.m} replicas "
                  f"(predicted p50 {plan.predicted_time*1e3:.1f} ms)")
        else:
            print(f"capacity plan: no feasible operating point "
                  f"({plan.reason})")

    router = None
    if args.router:
        n_replicas = args.replicas
        if n_replicas <= 0:
            n_replicas = plan.m if plan else 2
            print(f"router: --replicas 0 -> planner min-replicas answer "
                  f"m={n_replicas}")
        router = _run_router(args, eng, specs, reqs, n_replicas,
                             prefill_chunk)

    if args.trace:
        trace_events = (router.all_events() if router is not None
                        else list(eng.events()))
        busy = sum(e.step_s for e in trace_events
                   if getattr(e, "kind", "") == "serve_step")
        _export_trace(args, list(trace_events) + tune_evs, planner, busy,
                      eng.cfg.n_layers)

    ok = _verify_prefix_reuse(args.arch, args.smoke, eng, probes)
    if not ok:
        print("FAIL: prefix-reuse verification")
        sys.exit(1)


if __name__ == "__main__":
    main()
