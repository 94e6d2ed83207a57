"""Drive one cell: build, warm, serve the seeded traffic, measure, check.

The program is driven only through its public serving entry points
(``ServeEngine.submit``/``step``); the
benchmark records, from its own side, when each request was due, when it
was sent and when each of its tokens came back (host clock after the step
that produced it, which ends in a device sync).
"""
from __future__ import annotations

import dataclasses
import gc
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import check, spec, traffic
from bench import trace as trace_mod
from bench.peaks import peaks as chip_peaks


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# program side
# ---------------------------------------------------------------------------
def engine_class(cfg):
    """``ServeEngine`` serving ``cfg`` (the cut configuration) in place of
    the registry entry its name would load."""
    from repro.serve import ServeEngine

    class BenchEngine(ServeEngine):
        config_for = staticmethod(lambda arch, smoke: cfg)

    return BenchEngine


class Clock:
    """perf_counter for one engine's span tracer; remembers its first
    reading, the tracer's epoch, so spans can be put on the run's clock."""

    def __init__(self):
        self.first: Optional[float] = None

    def __call__(self) -> float:
        t = time.perf_counter()
        if self.first is None:
            self.first = t
        return t


class CompileMeter:
    """Programs made ready (compiled, or loaded from the persistent cache)
    while started."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n, self.seconds = 0, 0.0

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration

    def start(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def stop(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)


def build(cell: spec.Cell, cfg, seed: int, device, trace: bool):
    """(engine, clock): the engine at the cell's geometry with the
    benchmark's weights on ``device``, laid out by the configuration's
    block module, and its span tracer's clock."""
    from bench.weights import check_layout, make_weights

    g = cell.geometry
    clock = Clock()
    eng = engine_class(cfg)(
        cfg.name, smoke=False, max_batch=g["max_batch"],
        page_size=g["page_size"], max_seq=g["max_seq"],
        num_pages=g["num_pages"], seed=seed,
        params=make_weights(cell.blocks, cfg, seed, device),
        prefill_chunk=g["prefill_chunk"], trace=trace, trace_clock=clock)
    check_layout(cell.blocks, cfg, eng.lm.param_shapes())
    return eng, clock


def chunk_starts(cell: spec.Cell) -> List[int]:
    """Every chunk start the traffic can reach: prompts are drawn in whole
    granules, and a step's prefill budget is split between prompts at
    granule boundaries, so a chunk can start at any granule below the
    longest prompt."""
    granule = cell.traffic.get("granule", 1)
    return list(range(0, cell.traffic["prompt"]["max"], granule))


def warm(eng, cell: spec.Cell, seed: int) -> None:
    """Compile or load every program the traffic reaches, through the
    public path: the prefill chunk at every reachable start, then the
    decode step at max_batch and the small programs around it.

    A step's prefill budget (one chunk, C tokens) goes to prompts in the
    order they came, so a prompt of C - r tokens sent together with the
    longest prompt leaves it r tokens of the first step: its chunks then
    start at 0, r, r + C, r + 2C, ...  One such pair for each start
    modulo C reaches every start."""
    g = cell.geometry
    c = g["prefill_chunk"]
    longest = cell.traffic["prompt"]["max"]
    rng = np.random.default_rng(seed)

    def send(n: int, new_tokens: int) -> None:
        eng.submit(rng.integers(0, eng.cfg.vocab_size, n, dtype=np.int32),
                   new_tokens, arrival_step=eng.step_count)

    for r in sorted({s % c for s in chunk_starts(cell)}):
        if r:
            send(c - r, 1)
        send(longest, 1)
        eng.run()
    granule = cell.traffic.get("granule", 1)
    for i in range(g["max_batch"]):
        send(granule * (1 + i % 3), 3)
    eng.run()


# ---------------------------------------------------------------------------
# benchmark side: the serving loop
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Live:
    """One request as the benchmark sees it."""

    spec: traffic.RequestSpec
    due: float                  # host clock
    sent: float = 0.0
    request: object = None      # the engine's Request
    times: List[float] = dataclasses.field(default_factory=list)
    finished: float = math.inf
    caller: int = -1            # closed loop: the caller that sent it

    @property
    def generated(self) -> List[int]:
        return self.request.generated


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    number: int              # the engine's step number
    decode_lens: List[int]   # K/V length of each decode row


class Loop:
    """Serve the traffic open loop: each request is sent when it is due,
    whether or not the engine keeps up."""

    def __init__(self, engine, specs, t_start):
        self.engine = engine
        self.specs = list(specs)
        self.next = 0
        self.t_start = t_start
        self.live: List[Live] = []
        self.inflight: List[Live] = []
        self.steps: List[Step] = []
        self.refused = 0

    def _take(self) -> traffic.RequestSpec:
        if self.next >= len(self.specs):
            raise RuntimeError("the traffic ran out of requests; raise the "
                               "mix's count")
        self.next += 1
        return self.specs[self.next - 1]

    def _send(self, due: float, caller: int = -1) -> bool:
        """Send the next request, due at ``due``; False if it was refused."""
        s = self._take()
        rec = Live(spec=s, due=due, sent=time.perf_counter(), caller=caller)
        try:
            rec.request = self.engine.submit(s.prompt, s.max_new_tokens,
                                             arrival_step=self.engine.step_count)
        except ValueError as e:
            log(f"bench: request refused: {e}")
            self.refused += 1
            return False
        self.live.append(rec)
        self.inflight.append(rec)
        return True

    def _finished(self, rec: Live) -> None:
        """``rec`` received its last token at ``rec.finished``."""

    def _next_due(self) -> float:
        if self.next < len(self.specs):
            return self.t_start + self.specs[self.next].due_s
        return math.inf

    def _send_due(self, now: float) -> None:
        while self._next_due() <= now:
            self._send(self._next_due())

    def busy(self) -> bool:
        return not self.engine.scheduler.drained

    def step(self) -> None:
        import jax

        before = [len(r.generated) for r in self.inflight]
        number = self.engine.step_count
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            self.engine.step()
        t1 = time.perf_counter()
        lens: List[int] = []
        keep = []
        for rec, n0 in zip(self.inflight, before):
            n1 = len(rec.generated)
            if n1 > n0:
                rec.times += [t1] * (n1 - n0)
                if n0 > 0:  # a decode row: K/V of prompt and n0 tokens
                    lens.append(len(rec.spec.prompt) + n0)
            if n1 >= rec.spec.max_new_tokens:
                rec.finished = t1
                self._finished(rec)
            else:
                keep.append(rec)
        self.inflight = keep
        self.steps.append(Step(t0, t1, number, lens))

    def run_until(self, t_end: float, on_time=None) -> None:
        import jax

        while True:
            now = time.perf_counter()
            if on_time is not None:
                on_time(now)
            if now >= t_end:
                return
            self._send_due(now)
            if self.busy():
                self.step()
                continue
            nxt = min(t_end, self._next_due())
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, nxt - time.perf_counter()))


class ClosedLoop(Loop):
    """Serve the traffic closed loop: ``callers`` callers, each with at most
    one request in flight.  A caller's first request falls due at its point
    of an even spread over ``ramp_s``; each later one at the host-clock time
    its previous one finished (the end of the step that produced its last
    token), with no think time."""

    def __init__(self, engine, specs, t_start, callers: int, ramp_s: float):
        super().__init__(engine, [], t_start)
        self.source = specs
        # when each caller's next request falls due; inf while it waits
        self.due = [t_start + i * ramp_s / callers for i in range(callers)]

    def _take(self) -> traffic.RequestSpec:
        return next(self.source)

    def _next_due(self) -> float:
        return min(self.due)

    def _send_due(self, now: float) -> None:
        while self._next_due() <= now:
            due = self._next_due()
            caller = self.due.index(due)
            self.due[caller] = math.inf
            if not self._send(due, caller):  # refused: the caller goes on
                self.due[caller] = time.perf_counter()

    def _finished(self, rec: Live) -> None:
        self.due[rec.caller] = rec.finished


def serving_loop(engine, cell: spec.Cell, vocab: int, seed: int,
                 horizon_s: float) -> Loop:
    """The loop that serves the cell's traffic mix, started now."""
    mix, g = cell.traffic, cell.geometry
    if mix["arrivals"] == "closed":
        specs = traffic.requests(mix, g, vocab, seed)
        return ClosedLoop(engine, specs, time.perf_counter(), g["callers"],
                          g["ramp_s"])
    specs = traffic.generate(mix, g, vocab, seed, horizon_s)
    return Loop(engine, specs, time.perf_counter())


# ---------------------------------------------------------------------------
# end-to-end metrics
# ---------------------------------------------------------------------------
def pct(values, q: float) -> float:
    """The q-th percentile (linear between order statistics)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(loop: Loop, w0: float, w1: float, setup_s: float) -> Dict:
    ttft = [r.times[0] - r.due for r in loop.live
            if r.times and w0 < r.times[0] <= w1]
    gaps = [b - a for r in loop.live for a, b in zip(r.times, r.times[1:])
            if w0 < b <= w1]
    tokens = sum(1 for r in loop.live for t in r.times if w0 < t <= w1)
    out = {"setup_s": setup_s}
    if ttft:
        out["ttft_p80_ms"] = pct(ttft, 80) * 1e3
    if gaps:
        out["itl_p50_ms"] = pct(gaps, 50) * 1e3
        out["itl_p90_ms"] = pct(gaps, 90) * 1e3
    log(f"bench: window {w1 - w0:.3f} s: {len(ttft)} first tokens, "
        f"{len(gaps)} token gaps, {tokens} tokens")
    if ttft and gaps:
        def ms(values, qs):
            return " ".join(f"p{q} {pct(values, q) * 1e3:.3f}" for q in qs)
        log(f"bench: ttft ms {ms(ttft, (50, 70, 80, 90, 100))}; "
            f"token gap ms {ms(gaps, (50, 75, 90, 95, 99, 100))}")
    return out


# ---------------------------------------------------------------------------
# per-layer metrics: one reader per metric, found by name
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class RunData:
    """What a per-layer metric's reader may read."""

    cfg: object
    geometry: Dict
    peaks: Dict
    w0: float
    w1: float
    loop: Loop
    engine: object
    clock: Clock
    trace: Optional[trace_mod.Summary]
    trace_t0: float = 0.0   # host clock when the profiler started

    def note(self, msg: str) -> None:
        log(f"bench: {msg}")

    def window_steps(self) -> List[Step]:
        return [s for s in self.loop.steps if self.w0 <= s.t0 and s.t1 <= self.w1]

    def step_events(self, op: str):
        """(ServeStepEvent, Step) of kind ``op`` in the window's steps."""
        steps = {s.number: s for s in self.window_steps()}
        return [(ev, steps[ev.step]) for ev in self.engine.events("serve_step")
                if ev.op == op and ev.step in steps]

    def spans(self, name: str):
        """(SpanEvent, start on the run's clock) of the spans named ``name``."""
        first = self.clock.first or 0.0
        return [(ev, first + ev.t0) for ev in self.engine.events("span")
                if ev.name == name]


def load_reader(root: Path, name: str):
    return spec.load_module(root / "bench" / "metrics" / f"{name}.py",
                            f"bench_metric_{name}").read


def per_layer(cell: spec.Cell, data: RunData) -> Dict:
    out = {}
    for m in cell.per_layer:
        value = load_reader(cell.root, m["name"])(data)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
def use_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at the checkout's fixed
    ``.jax_cache/``, whatever the environment says, so that only a cell's
    first run in a checkout compiles."""
    import jax

    cache_dir = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def run_cell(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
             devices, t_process: float, trace_dir: Path,
             trace_seconds: float, smoke: bool = False, judge=None) -> Dict:
    """One run of ``cell``; ``judge`` (default ``check.run``) decides
    ``correct`` from the served sample once the program's state is freed."""
    import jax

    cache_dir = use_compile_cache(cell.root)
    meter = CompileMeter().start()
    cfg = spec.arch_config(cell.config, smoke)
    g = cell.geometry
    dev = devices[0]
    log(f"bench: {cell.name} seed {seed} on {dev.device_kind} x{len(devices)}; "
        f"{cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"vocab={cfg.vocab_size}; compile cache {cache_dir}")

    engine, clock = build(cell, cfg, seed, dev, trace)
    t = time.perf_counter()
    warm(engine, cell, seed)
    log(f"bench: built and warmed at {t - t_process:.1f} s + "
        f"{time.perf_counter() - t:.1f} s; {meter.n} programs compiled or "
        f"loaded in {meter.seconds:.1f} s")

    ramp = g["ramp_s"]
    loop = serving_loop(engine, cell, cfg.vocab_size, seed, ramp + seconds + 5.0)
    loop.run_until(loop.t_start + ramp)
    w0 = time.perf_counter()  # the window opens at a step boundary
    setup_s = w0 - t_process
    n_compiled = meter.n

    profiling = {"on": False, "ann": None, "t0": 0.0, "t1": 0.0}
    w_end = w0 + seconds

    def on_time(now: float) -> None:
        if trace and not profiling["on"] and now >= w_end - trace_seconds:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            profiling["ann"] = jax.profiler.TraceAnnotation("bench.window")
            profiling["ann"].__enter__()
            profiling["on"] = True
            profiling["t0"] = time.perf_counter()

    loop.run_until(w_end, on_time)
    w1 = loop.steps[-1].t1 if loop.steps and loop.steps[-1].t1 > w_end else w_end
    summary = None
    if profiling["on"]:
        profiling["ann"].__exit__(None, None, None)
        jax.profiler.stop_trace()
        traced = trace_mod.load(trace_mod.find_xplane(str(trace_dir)))
        if traced.devices or not smoke:  # the CPU rehearsal traces no TPU
            summary = trace_mod.summarize(traced)
    in_window = meter.n - n_compiled
    meter.stop()
    lateness = [r.sent - r.due for r in loop.live if w0 < r.due <= w1]
    log(f"bench: {in_window} programs compiled inside the window")
    if lateness:
        log(f"bench: generator lateness p50 {pct(lateness, 50) * 1e3:.3f} ms, "
            f"max {max(lateness) * 1e3:.3f} ms over {len(lateness)} requests")

    peak = memory_peak(devices)
    data = RunData(cfg, g, chip_peaks(dev.device_kind) if not smoke else
                   {"bf16_flops": 1.0, "hbm_bytes_s": 1.0, "hbm_bytes": 1.0},
                   w0, w1, loop, engine, clock, summary,
                   profiling["t0"])
    if trace:
        metrics = per_layer(cell, data)
    else:
        e2e = end_to_end(loop, w0, w1, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in e2e}
    attempted = sum(1 for r in loop.live if w0 < r.due <= w1) + loop.refused
    finished = [r for r in loop.live if w0 < r.finished <= w1]
    sample = check.sample(finished, seed, g["check_requests"])
    refused = loop.refused
    del data, loop, engine, clock
    gc.collect()
    log(f"bench: memory peak {peak} bytes; "
        f"{(dev.memory_stats() or {}).get('bytes_in_use')} in use once the "
        "program's state is freed, before the reference")
    verdict = (judge or check.run)(cell.blocks, cfg, seed, sample, g, devices[0])
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": verdict.correct and in_window == 0,
              "attempted": attempted, "failed": refused,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    compared = dict(verdict.compared)
    compared["compiles_in_window"] = {"value": in_window, "limit": 0}
    for name, c in compared.items():
        log(f"compared {name}: {c['value']} limit {c['limit']}")
    result["compared"] = compared
    return result
