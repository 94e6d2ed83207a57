#!/usr/bin/env python3
"""Smoke run of the serve engine on TPU: stablelm-1.6b at its published width
and depth (24 layers, d_model 2048, vocab 100,352, random weights from
``--seed``), served through ``ServeEngine`` / ``Router`` with chunked prefill
and the paged Pallas decode kernel compiled for the chip.

    python3 chip_smoke.py               # one chip: the engine's main path
    python3 chip_smoke.py --chips 4     # four chips: routed replicas + TP=4
    JAX_PLATFORMS=cpu python3 chip_smoke.py --smoke [--chips 4]

One chip serves 8 seeded requests (prompts of 64-512 tokens, 32 new tokens
each, max_batch 8, page 16, prefill chunk 256) and checks that

* the engine picked the Pallas paged decode and it lowered to a Mosaic
  kernel (``tpu_custom_call``), not the interpreter;
* the same trace on the paged-native jnp ``stream`` decode gives logits
  within ``TOL_IMPL`` wherever both engines saw the same context, and the
  same greedy tokens except where the two picks tie at that tolerance: the
  logits are bfloat16, and among 100,352 random-weight logits the top two
  are often within an ulp, so two correct paths that round differently can
  part at a tie (and then serve different contexts);
* each finished request of either engine, re-run teacher-forced through
  ``LM.prefill``, gives last-position logits within ``TOL_TEACHER`` of the
  engine's final decode logits; every logit is finite.

``--chips 4`` runs only the four-chip phase: four one-chip replicas behind
``Router``, each pinned to its own device, must be token-identical to one
engine serving the trace; a 4-way tensor-parallel engine's teacher-forced
logits must be within ``TOL_TP`` of the one-chip engine's.

``--smoke`` is the rehearsal: the reduced config, on any backend (Pallas in
interpret mode off the chip), with no result line.  Without it the script
refuses any backend but a TPU.  Step times printed are smoke timings of one
short trace (first call includes compilation), not benchmark numbers.  The
last line of a passing chip run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
ARCH = "stablelm-1.6b"
N_REQUESTS, GEN = 8, 32
PROMPT_MIN, PROMPT_MAX = 64, 512
MAX_BATCH, PAGE, CHUNK = 8, 16, 256
# one arrival every ceil(PROMPT_MAX / CHUNK) steps: a prompt's chunked
# prefill ends before the next arrives, so no two prompts split one step's
# token budget and every chunk starts at a multiple of CHUNK (the chunk
# program is compiled once per start, s0 being static)
ARRIVAL_GAP = -(-PROMPT_MAX // CHUNK)

# Tolerances are on max|a - b| / max|b| over a request's logits row, in
# units of bfloat16's epsilon (2**-7): the model computes in bfloat16, so two
# paths that round at different points differ by a few ulps of the logits'
# scale, while a wrong page, mask or position moves them by O(1).
BF16_EPS = 2.0 ** -7
# Pallas vs stream: the same blocked online softmax with every dot at
# HIGHEST; only the f32 accumulation order inside the dots differs, and it
# reaches the logits through the bfloat16 attention output of 24 layers.
# Two picks tie when they score within 2 * TOL_IMPL in one row (each
# engine's row may be TOL_IMPL off).
TOL_IMPL = 4 * BF16_EPS
# Engine vs teacher-forced prefill: the prompt's K/V came from chunked
# prefill and the rest from 31 decode steps, against one flash pass over the
# whole sequence — different blockings of the softmax in every layer.
TOL_TEACHER = 8 * BF16_EPS
# TP=4 vs one chip: the same prefill with each matmul's contraction split
# over four devices and summed in another order.
TOL_TP = 8 * BF16_EPS


def log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Named pass/fail results; any failure makes the run exit 1."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str) -> None:
        log(f"check {name}: {'PASS' if ok else 'FAIL'} ({detail})")
        if not ok:
            self.failed.append(name)


class CompileMeter:
    """Programs made ready since ``start``: their count and seconds (JAX's
    backend-compile event covers a compile and a load from the persistent
    cache alike) and how many were persistent-cache hits."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.n, self.seconds, self.hits = 0, 0.0, 0

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration

    def _event(self, event: str, **_) -> None:
        if event == self.HIT:
            self.hits += 1

    def start(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def stop(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def __str__(self) -> str:
        return (f"{self.n} programs compiled or loaded in {self.seconds:.1f} s "
                f"({self.hits} from the persistent cache)")


# ---------------------------------------------------------------------------
# trace and engines
# ---------------------------------------------------------------------------
def trace_specs(vocab: int, seed: int):
    """(prompt, max_new_tokens, arrival_step) for each request."""
    rng = np.random.RandomState(seed)
    lens = rng.randint(PROMPT_MIN, PROMPT_MAX + 1, N_REQUESTS)
    lens[:2] = PROMPT_MAX, PROMPT_MIN  # span the whole range
    return [
        (rng.randint(0, vocab, int(n)).astype(np.int32), GEN, i * ARRIVAL_GAP)
        for i, n in enumerate(lens)
    ]


def max_seq_for(specs) -> int:
    need = max(len(p) + g for p, g, _ in specs)
    return -(-need // PAGE) * PAGE


def trace_for(smoke: bool, seed: int):
    """The trace and the ``max_seq`` its longest request needs."""
    from repro.serve import ServeEngine

    specs = trace_specs(ServeEngine.config_for(ARCH, smoke).vocab_size, seed)
    return specs, max_seq_for(specs)


def make_engine(smoke: bool, seed: int, max_seq: int, *, params=None,
                paged_impl=None, mesh=None):
    from repro.serve import ServeEngine

    rt = ServeEngine.default_runtime(PAGE, paged_impl=paged_impl, mesh=mesh)
    return ServeEngine(
        ARCH, smoke=smoke, max_batch=MAX_BATCH, page_size=PAGE,
        max_seq=max_seq, seed=seed, params=params, rt=rt,
        prefill_chunk=CHUNK, collect_logits=True,
    )


def serve(eng, specs):
    reqs = [eng.submit(p, g, arrival_step=a) for p, g, a in specs]
    t0 = time.perf_counter()
    eng.run()
    return reqs, time.perf_counter() - t0


def step_times(eng, label: str) -> None:
    """Print the engine's prefill-chunk and decode step times (host clock,
    each step ends in a device sync)."""
    for op in ("prefill", "decode"):
        ts = [e.step_s for e in eng.events("serve_step") if e.op == op]
        if not ts:
            continue
        rest = ts[1:] or ts
        log(f"smoke timing [{label}] {op}: {len(ts)} steps, first "
            f"{ts[0] * 1e3:.1f} ms (includes compile), median of the rest "
            f"{float(np.median(rest)) * 1e3:.3f} ms, max "
            f"{max(rest) * 1e3:.3f} ms")


def rel_err(a, b) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(float(np.max(np.abs(b))), 1e-30))


def all_finite(reqs) -> bool:
    return all(np.isfinite(row).all() for r in reqs for row in r.logits_trace)


def teacher_forced(prefill, params, reqs):
    """Last-position logits of ``prefill`` (a jitted ``LM.prefill``) over
    each request's prompt plus every generated token but the last: the
    input of the engine's final decode step."""
    import jax.numpy as jnp

    out = []
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.generated[:-1], np.int32)])
        logits, _ = prefill(params, jnp.asarray(seq)[None])
        out.append(np.asarray(logits[0], np.float32))
    return out


def check_teacher_forced(check, name, prefill, params, reqs) -> None:
    tf = teacher_forced(prefill, params, reqs)
    errs = [rel_err(r.logits_trace[-1], x) for r, x in zip(reqs, tf)]
    check(name, all(np.isfinite(x).all() for x in tf)
          and max(errs) <= TOL_TEACHER,
          f"max rel err {max(errs):.3e} <= {TOL_TEACHER:.3e} "
          f"(per request {[float(f'{e:.2e}') for e in errs]})")


def compare_streams(ref_reqs, reqs):
    """Two engines' greedy streams, request by request.  Returns (parts,
    err): ``parts`` lists (request, token, gap) for each request whose
    streams part, ``gap`` being how far the other engine's token scores
    below the reference's pick in the reference's row, relative to
    max|logit|; ``err`` is the max relative logits error over every row
    both engines computed from the same context (up to and including the
    step where they part)."""
    parts, err = [], 0.0
    for a, b in zip(ref_reqs, reqs):
        t = next((i for i, (x, y) in enumerate(zip(a.generated, b.generated))
                  if x != y), None)
        rows = len(a.generated) if t is None else t + 1
        err = max([err] + [rel_err(b.logits_trace[i], a.logits_trace[i])
                           for i in range(rows)])
        if t is not None:
            row = np.asarray(a.logits_trace[t], np.float32)
            gap = row[a.generated[t]] - row[b.generated[t]]
            parts.append((a.rid, t, float(gap / np.abs(row).max())))
    return parts, err


def pinned(device):
    from repro.launch.mesh import make_debug_mesh

    return make_debug_mesh(1, 1, devices=[device])


def devices_of(tree):
    import jax

    return {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}


def peak_memory(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} bytes ({peak / 2**30:.2f} GiB)"


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def one_chip_phase(smoke: bool, seed: int, check: Checks) -> None:
    import jax

    on_tpu = jax.default_backend() == "tpu"
    meter = CompileMeter().start()
    try:
        specs, max_seq = trace_for(smoke, seed)
        t0 = time.perf_counter()
        # off the chip the engine would pick stream; ask for the kernel so
        # the rehearsal runs it (in interpret mode)
        eng = make_engine(smoke, seed, max_seq,
                          paged_impl=None if on_tpu else "pallas")
        log(f"engine: {eng.cfg.name} layers={eng.cfg.n_layers} "
            f"d_model={eng.cfg.d_model} vocab={eng.cfg.vocab_size} "
            f"max_seq={eng.max_seq} pages={eng.pool.num_pages} "
            f"paged_impl={eng.rt.paged_impl}; built in "
            f"{time.perf_counter() - t0:.1f} s")
        log("trace: prompt lengths " + str([len(p) for p, _, _ in specs])
            + f", {GEN} new tokens each, arrivals every {ARRIVAL_GAP} steps")
        check("main_path_impl", eng.rt.paged_impl == "pallas",
              f"engine chose paged_impl={eng.rt.paged_impl}")

        reqs, wall = serve(eng, specs)
        log(f"served {len(reqs)} requests in {eng.step_count} steps, "
            f"{wall:.2f} s wall (pallas decode)")
        step_times(eng, "pallas")
        check("finished", all(len(r.generated) == GEN for r in reqs),
              f"{sum(len(r.generated) == GEN for r in reqs)}/{len(reqs)} "
              f"requests produced {GEN} tokens")
        if on_tpu:
            import jax.numpy as jnp

            text = eng._decode.lower(
                eng.params, jnp.asarray(eng.next_tokens),
                jnp.asarray(eng.lengths), eng.cache, eng.page_tables_dev,
            ).as_text()
            check("kernel_native", "tpu_custom_call" in text,
                  "decode step lowers the paged kernel to a Mosaic custom call")

        ref = make_engine(smoke, seed, eng.max_seq, params=eng.params,
                          paged_impl="stream")
        ref_reqs, wall = serve(ref, specs)
        step_times(ref, "stream")
        parts, err = compare_streams(ref_reqs, reqs)
        check("pallas_vs_stream_tokens",
              all(gap <= 2 * TOL_IMPL for _, _, gap in parts),
              f"{len(reqs) - len(parts)}/{len(reqs)} streams identical; "
              f"(request, token, gap) where they part: "
              f"{[(r, t, float(f'{g:.2e}')) for r, t, g in parts]}")
        check("pallas_vs_stream_logits", err <= TOL_IMPL,
              f"max rel err {err:.3e} <= {TOL_IMPL:.3e} over same-context rows")
        check("finite", all_finite(reqs) and all_finite(ref_reqs),
              "every logits row of both engines is finite")

        prefill = jax.jit(eng.lm.prefill)
        check_teacher_forced(check, "teacher_forced_pallas", prefill,
                             eng.params, reqs)
        check_teacher_forced(check, "teacher_forced_stream", prefill,
                             eng.params, ref_reqs)
    finally:
        meter.stop()
    log(f"compiles: {meter}; per jit: decode pallas="
        f"{eng._decode._cache_size()} stream={ref._decode._cache_size()}, "
        f"prefill chunk pallas="
        f"{eng._chunk._cache_size()} stream={ref._chunk._cache_size()}")
    log(f"peak device memory: {peak_memory(jax.devices()[0])}")


def four_chip_phase(smoke: bool, seed: int, check: Checks) -> None:
    import jax

    from repro.launch.mesh import make_debug_mesh
    from repro.serve import Router

    devices = jax.devices()
    if len(devices) < 4:
        raise SystemExit(f"--chips 4 needs 4 devices, found {len(devices)}")
    meter = CompileMeter().start()
    try:
        specs, max_seq = trace_for(smoke, seed)
        ref = make_engine(smoke, seed, max_seq, mesh=pinned(devices[0]))
        ref_reqs, wall = serve(ref, specs)
        log(f"one engine on {devices[0]}: {len(ref_reqs)} requests in "
            f"{wall:.2f} s (paged_impl={ref.rt.paged_impl})")

        replicas = [
            make_engine(smoke, seed, max_seq, params=ref.params,
                        mesh=pinned(devices[i]))
            for i in range(4)
        ]
        placed = [devices_of((e.params, e.cache)) for e in replicas]
        check("replica_placement",
              all(p == {devices[i]} for i, p in enumerate(placed)),
              "replica params+cache on " + str([sorted(map(str, p)) for p in placed]))
        router = Router(replicas)
        routed = [router.submit(p, g, arrival_step=a) for p, g, a in specs]
        t0 = time.perf_counter()
        stats = router.run()
        log(f"router: {stats['dispatched']} requests over 4 replicas "
            f"{stats['dispatch_per_replica']} in "
            f"{time.perf_counter() - t0:.2f} s")
        for i, e in enumerate(replicas):
            step_times(e, f"replica {i}")
        identical = all(rr.generated == r.generated
                        for rr, r in zip(routed, ref_reqs))
        check("routed_vs_one_engine", identical
              and min(stats["dispatch_per_replica"]) > 0,
              "every request's tokens identical to the one engine's; "
              "every replica served requests")
        del router, routed, replicas
        gc.collect()  # free the replicas' caches before the TP engine

        tp = make_engine(smoke, seed, max_seq, params=ref.params,
                         mesh=make_debug_mesh(1, 4))
        log(f"tensor parallel engine: mesh {dict(tp.plan.mesh.shape)}, "
            f"paged_impl={tp.rt.paged_impl}")
        one = teacher_forced(jax.jit(ref.lm.prefill), ref.params, ref_reqs)
        four = teacher_forced(jax.jit(tp.lm.prefill), tp.params, ref_reqs)
        errs = [rel_err(a, b) for a, b in zip(four, one)]
        check("tp4_vs_one_chip", all(np.isfinite(x).all() for x in four)
              and max(errs) <= TOL_TP,
              f"teacher-forced max rel err {max(errs):.3e} <= {TOL_TP:.3e}")
    finally:
        meter.stop()
    log(f"compiles: {meter}")
    for d in devices[:4]:
        log(f"peak device memory {d}: {peak_memory(d)}")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip main path (default); 4: only the "
                         "routed-replica and tensor-parallel phase")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="rehearsal: reduced config on any backend, no "
                         "result line")
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu" and not args.smoke:
        print(f"chip_smoke: JAX found no TPU (backend "
              f"{jax.default_backend()!r}); nothing was run", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    log(f"jax {jax.__version__}; backend {jax.default_backend()}; device "
        f"{dev.device_kind!r} x{len(jax.devices())}; compile cache "
        f"{enable_compile_cache()}")
    log("timings below are smoke timings of one short trace, not benchmark "
        "numbers")
    check = Checks()
    phase = four_chip_phase if args.chips == 4 else one_chip_phase
    t0 = time.perf_counter()
    phase(args.smoke, args.seed, check)
    log(f"phase wall time {time.perf_counter() - t0:.1f} s")
    if check.failed:
        log(f"FAILED: {', '.join(check.failed)}")
        return 1
    if args.smoke:
        log("rehearsal passed (smoke config; no result line)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
