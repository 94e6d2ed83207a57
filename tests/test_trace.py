"""PR-9 observability: spans, attribution, SLO burn rates, percentiles.

Covers the trace subsystem contract end to end: deterministic span
identity (same seed -> byte-identical Perfetto exports), nesting
invariants (children link to parents and never out-time them), the
Perfetto schema validator, predicted-vs-measured attribution with
kernel rows joined from the tune cache, the P² streaming percentile
estimator against exact numpy quantiles, sink/tracker context managers
and torn-tail recovery, ordered ``log_from_device`` emission under jit,
and the SLO burn-rate monitor — including the headline claim that it
fires *before* the PR-7 drift detector on a sustained 2x slowdown, at
stream level and through the fleet scheduler.
"""
import json
import math

import numpy as np
import pytest

from repro.telemetry import (
    JSONLSink,
    MemorySink,
    P2Quantile,
    ServeStepEvent,
    SloAlertEvent,
    SpanEvent,
    StatsSink,
    Tracker,
    TuneEvent,
    read_events,
)
from repro.telemetry.refit import DriftConfig, DriftDetector
from repro.telemetry.trace import (
    CountingClock,
    SloConfig,
    SLOMonitor,
    SpanTracer,
    attribute,
    det_id,
    flame_summary,
    format_attribution,
    format_tree,
    monitor_serve_events,
    span_roots,
    to_perfetto,
    validate_perfetto,
    write_perfetto,
)


# ------------------------------------------------------- deterministic ids
def test_det_id_is_stable_and_distinct():
    assert det_id("trace", "serve", 0) == det_id("trace", "serve", 0)
    assert det_id("trace", "serve", 0) != det_id("trace", "serve", 1)
    assert len(det_id("x")) == 16
    int(det_id("x"), 16)  # hex


def test_same_seed_traces_have_identical_ids():
    def run():
        tr = SpanTracer(trace=("serve", "m", 0, 0), clock=CountingClock())
        with tr.span("step", step=0, component="engine.step"):
            with tr.span("decode", step=0, component="engine.decode", batch=2):
                pass
            tr.emit_span("join", dur=0.0, step=0, component="scheduler.join")
        return tr.tracker.events("span")

    a, b = run(), run()
    assert [e.span_id for e in a] == [e.span_id for e in b]
    assert [e.parent_id for e in a] == [e.parent_id for e in b]
    assert a[0].trace_id == b[0].trace_id


def test_same_seed_perfetto_exports_are_byte_identical(tmp_path):
    paths = []
    for i in range(2):
        tr = SpanTracer(trace=("run", 7), clock=CountingClock())
        with tr.span("outer", step=0):
            with tr.span("inner", step=0):
                pass
        p = tmp_path / f"trace_{i}.json"
        write_perfetto(p, tr.tracker.events("span"))
        paths.append(p)
    assert paths[0].read_bytes() == paths[1].read_bytes()


# ------------------------------------------------- the profiler's own trace
def _profiled(log_dir, body):
    """Run ``body()`` inside a profiler session writing under ``log_dir``;
    returns the host events of the trace, by name."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(log_dir))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    return {
        e.name: (e.start_ns, e.start_ns + e.duration_ns)
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
    }


def test_span_lands_in_the_profiler_trace_inside_its_parent(tmp_path):
    tr = SpanTracer(trace=("prof",), clock=CountingClock())

    def body():
        with tr.span("step", component="engine.step"):
            with tr.span("fetch", component="engine.decode.fetch"):
                sum(range(1000))

    host = _profiled(tmp_path, body)
    (s0, s1), (c0, c1) = host["engine.step"], host["engine.decode.fetch"]
    assert s0 <= c0 < c1 <= s1
    # the bus still carries both spans, the child first
    assert [e.component for e in tr.tracker.events("span")] == [
        "engine.decode.fetch", "engine.step"]


def test_emit_span_writes_no_profiler_event(tmp_path):
    tr = SpanTracer(trace=("prof",), clock=CountingClock())

    def body():
        with tr.span("tick", component="fleet.tick"):
            tr.emit_span("job", dur=300.0, component="fleet.train")

    host = _profiled(tmp_path, body)
    assert "fleet.tick" in host and "fleet.train" not in host
    assert len(tr.tracker.events("span")) == 2


def test_counting_clock_trace_is_byte_identical_under_the_profiler(tmp_path):
    """Two same-seed runs, one inside a profiler session: the spans on the
    bus do not see the profiler."""
    def run(i):
        tr = SpanTracer(trace=("run", 7), clock=CountingClock())
        with tr.span("step", step=0, component="engine.step"):
            with tr.span("sample", step=0, component="engine.sample", batch=2):
                pass
            tr.emit_span("join", dur=0.0, step=0, component="scheduler.join")
        p = tmp_path / f"trace_{i}.jsonl"
        tr.tracker.to_jsonl(p)
        return p.read_bytes()

    plain = run(0)
    out = {}
    host = _profiled(tmp_path / "prof", lambda: out.update(bytes=run(1)))
    assert "engine.sample" in host
    assert out["bytes"] == plain


def test_set_trace_rekeys_only_before_first_span():
    tr = SpanTracer(trace=("serve", "m", 0, -1))
    old = tr.trace_id
    tr.set_trace("serve", "m", 0, 3, replica=3)
    assert tr.trace_id != old and tr.replica == 3
    with tr.span("s"):
        pass
    with pytest.raises(RuntimeError):
        tr.set_trace("serve", "m", 0, 4)


# ------------------------------------------------------- nesting invariants
def test_span_nesting_parent_links_and_durations():
    tr = SpanTracer(trace=("nest",), clock=CountingClock())
    with tr.span("parent", step=1, component="engine.step") as ph:
        with tr.span("child_a", step=1, component="engine.decode"):
            pass
        with tr.span("child_b", step=1, component="engine.verify"):
            pass
    evs = tr.tracker.events("span")
    # close order: children emit before the parent
    assert [e.name for e in evs] == ["child_a", "child_b", "parent"]
    parent = evs[-1]
    kids = evs[:-1]
    assert parent.span_id == ph.span_id
    assert all(k.parent_id == parent.span_id for k in kids)
    assert all(k.trace_id == parent.trace_id for k in kids)
    # children start within the parent and their summed time fits inside it
    assert all(k.t0 >= parent.t0 for k in kids)
    assert sum(k.dur for k in kids) <= parent.dur + 1e-12
    assert [r.name for r in span_roots(evs)] == ["parent"]


def test_emit_span_parents_to_open_scope():
    tr = SpanTracer(trace=("emit",), clock=CountingClock())
    with tr.span("outer") as h:
        tr.emit_span("marker", dur=0.0, component="scheduler.join", wait_steps=4)
    evs = tr.tracker.events("span")
    marker = [e for e in evs if e.name == "marker"][0]
    assert marker.parent_id == h.span_id
    assert marker.dur == 0.0 and marker.attrs["wait_steps"] == 4


def test_span_handle_annotations():
    tr = SpanTracer(trace=("attrs",), clock=CountingClock())
    with tr.span("decode", component="engine.decode", batch=4) as h:
        h.set(rows=2).predict(0.125)
    (ev,) = tr.tracker.events("span")
    assert ev.attrs == {"batch": 4, "rows": 2}
    assert ev.predicted_s == 0.125


# ------------------------------------------------------------ export layer
def _demo_spans():
    tr = SpanTracer(trace=("demo",), replica=0, clock=CountingClock())
    for step in range(3):
        with tr.span("step", step=step, component="engine.step"):
            with tr.span("decode", step=step, component="engine.decode",
                         predicted_s=0.002, batch=2):
                pass
    return tr.tracker.events("span")


def test_perfetto_schema_valid_and_loadable(tmp_path):
    evs = _demo_spans()
    payload = to_perfetto(evs)
    assert validate_perfetto(payload) == []
    xs = [e for e in payload["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == len(evs)
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in xs)
    out = tmp_path / "t.json"
    write_perfetto(out, evs)
    again = json.loads(out.read_text())
    assert validate_perfetto(again) == []


def test_perfetto_validator_catches_corruption():
    payload = to_perfetto(_demo_spans())
    xs = [e for e in payload["traceEvents"] if e["ph"] == "X"]
    xs[0]["args"]["parent_id"] = "feedfacefeedface"  # dangling link
    del xs[1]["name"]
    xs[2]["dur"] = -1.0
    errs = validate_perfetto(payload)
    assert len(errs) >= 3


def test_format_tree_and_flame_render():
    evs = _demo_spans()
    tree = format_tree(evs)
    assert "step" in tree and "decode" in tree
    assert sum(1 for ln in tree.splitlines()
               if ln.startswith("  decode")) == 3
    flame = flame_summary(evs)
    assert "engine.decode" in flame and "%" in flame


# ------------------------------------------------------------- attribution
def test_attribution_ratio_and_reconcile():
    evs = _demo_spans()  # decode spans carry predicted_s=0.002
    attr = attribute(evs)
    row = attr.row("engine.decode")
    assert row is not None and row.n == 3
    assert row.predicted_s == pytest.approx(0.006)
    assert row.ratio == pytest.approx(row.measured_s / 0.006)
    # root spans are the engine.step scopes: reconciliation against their
    # own summed wall time is exact by construction
    assert attr.reconcile(attr.total_measured_s, tol=0.0)
    assert not attr.reconcile(attr.total_measured_s * 2.0)


def test_attribution_kernel_rows_from_tune_cache():
    evs = list(_demo_spans())
    evs.append(TuneEvent(
        family="flash_decode_paged", shape={"b": 2, "d": 64},
        dtype="float32", backend="cpu", config={"block_b": 2},
        us_per_call=50.0,
    ))
    attr = attribute(evs, n_layers=4)
    row = attr.row("kernel/flash_decode_paged@b2")
    assert row is not None
    assert row.predicted_s == pytest.approx(4 * 50.0 * 1e-6)
    decode = [e for e in evs if getattr(e, "component", "") == "engine.decode"]
    assert row.measured_s == pytest.approx(
        sum(d.dur for d in decode) / len(decode))
    assert "kernel/flash_decode_paged@b2" in format_attribution(attr)


def test_attribution_prices_unpredicted_spans_via_planner():
    class FlatPlanner:
        def step_time(self, batch):
            return 0.004

    tr = SpanTracer(trace=("pl",), clock=CountingClock())
    with tr.span("decode", component="engine.decode", batch=4):
        pass
    attr = attribute(tr.tracker.events("span"), planner=FlatPlanner())
    row = attr.row("engine.decode")
    assert row.predicted_s == pytest.approx(0.004)


# -------------------------------------------------------------- P² sketch
def test_p2_quantile_matches_numpy():
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=0.0, sigma=0.6, size=4000)
    for p in (0.5, 0.95, 0.99):
        est = P2Quantile(p)
        for x in xs:
            est.observe(float(x))
        exact = float(np.percentile(xs, 100 * p))
        assert est.value() == pytest.approx(exact, rel=0.05)


def test_p2_quantile_exact_below_five_points():
    est = P2Quantile(0.5)
    for x in (5.0, 1.0, 3.0):
        est.observe(x)
    assert est.value() == pytest.approx(3.0)
    assert est.n == 3


def test_stats_sink_streams_percentiles():
    sink = StatsSink()
    for i in range(200):
        sink.write(ServeStepEvent(step=i, step_s=float(i), op="decode",
                                  batch=1, committed=1))
    fields = sink.summary()["serve_step"]["fields"]["step_s"]
    assert fields["p50"] == pytest.approx(99.5, rel=0.1)
    assert fields["p95"] == pytest.approx(189.0, rel=0.1)
    assert fields["p99"] == pytest.approx(197.0, rel=0.1)


# ------------------------------------------------- sinks, tails, ordering
def test_tracker_and_sinks_are_context_managers(tmp_path):
    path = tmp_path / "run.jsonl"
    with Tracker([MemorySink(), JSONLSink(path)]) as t:
        t.emit(ServeStepEvent(step=0, step_s=0.01, op="decode", batch=1,
                              committed=1))
    # closing the tracker closed (and flushed) the JSONL sink
    evs = read_events(path)
    assert len(evs) == 1 and evs[0].step_s == 0.01


def test_read_events_skips_torn_trailing_line(tmp_path):
    path = tmp_path / "torn.jsonl"
    with Tracker([JSONLSink(path)]) as t:
        for i in range(3):
            t.emit(ServeStepEvent(step=i, step_s=0.01, op="decode",
                                  batch=1, committed=1))
    whole = path.read_text()
    path.write_text(whole[:-20])  # writer died mid-append
    with pytest.warns(RuntimeWarning):
        evs = read_events(path)
    assert [e.step for e in evs] == [0, 1]


def test_read_events_raises_on_mid_file_corruption(tmp_path):
    path = tmp_path / "corrupt.jsonl"
    with Tracker([JSONLSink(path)]) as t:
        for i in range(3):
            t.emit(ServeStepEvent(step=i, step_s=0.01, op="decode",
                                  batch=1, committed=1))
    lines = path.read_text().splitlines()
    lines[1] = lines[1][:-15]  # torn in the middle: corruption, not a tail
    path.write_text("\n".join(lines) + "\n")
    with pytest.raises(json.JSONDecodeError):
        read_events(path)


def test_log_from_device_ordered_preserves_program_order():
    import jax
    import jax.numpy as jnp

    from repro.telemetry.tracker import log_from_device

    t = Tracker()

    @jax.jit
    def step(x):
        for i in range(4):
            x = x + 1.0
            log_from_device(
                t,
                lambda v, i=i: ServeStepEvent(step=i, step_s=float(v),
                                              op="decode", batch=1,
                                              committed=1),
                jnp.sum(x),
                ordered=True,
            )
        return x

    step(jnp.zeros((2,)))
    jax.effects_barrier()
    evs = t.events("serve_step")
    assert [e.step for e in evs] == [0, 1, 2, 3]
    assert [e.step_s for e in evs] == [2.0, 4.0, 6.0, 8.0]


# ------------------------------------------------------------- SLO monitor
def test_slo_monitor_quiet_on_healthy_stream():
    mon = SLOMonitor(SloConfig(target=1.0, window=8, min_points=2),
                     name="svc", objective="latency")
    for step in range(50):
        assert mon.observe(step, 0.5) is None
    assert mon.burn_rate == 0.0
    assert mon.budget_remaining() == 1.0


def test_slo_monitor_fires_fast_burn_then_cools_down():
    cfg = SloConfig(target=1.0, budget=0.05, window=8, burn_threshold=2.0,
                    min_points=2, cooldown=10)
    mon = SLOMonitor(cfg, name="svc", objective="latency")
    alerts = []
    for step in range(30):
        lat = 0.5 if step < 10 else 2.5
        a = mon.observe(step, lat)
        if a is not None:
            alerts.append(a)
    # one bad point in an 8-window is 12.5% bad vs a 5% budget = 2.5x burn:
    # the alert lands on the FIRST breached observation
    assert alerts[0].step == 10
    assert alerts[0].burn_rate >= cfg.burn_threshold
    # cooldown: next alert no earlier than 10 steps later
    assert len(alerts) >= 2 and alerts[1].step - alerts[0].step >= 10
    assert mon.budget_remaining() < 1.0


def test_slo_alert_event_round_trips():
    from repro.telemetry import from_dict

    ev = SloAlertEvent(step=5, slo="svc", objective="latency", target=1.0,
                       burn_rate=2.5, budget=0.05, window_bad=1, window=8,
                       budget_remaining=0.9)
    again = from_dict(json.loads(json.dumps(ev.to_dict())))
    assert again == ev


def test_slo_fires_before_drift_detector_on_2x_slowdown():
    """The headline ordering claim, at stream level: one latency stream, a
    sustained 2x slowdown at step 100 — the burn-rate monitor pages on the
    first breached point, the drift detector needs several residuals."""
    slo = SLOMonitor(SloConfig(target=1.2, budget=0.05, window=8,
                               burn_threshold=2.0, min_points=2),
                     name="svc", objective="latency")
    det = DriftDetector("svc", DriftConfig(window=8, threshold=0.25,
                                           min_points=4))
    slo_step = drift_step = None
    for step in range(200):
        lat = 1.0 if step < 100 else 2.0  # predicted stays 1.0
        if slo_step is None and slo.observe(step, lat) is not None:
            slo_step = step
        if drift_step is None and det.observe(step, 1.0, lat) is not None:
            drift_step = step
    assert slo_step is not None and drift_step is not None
    assert slo_step < drift_step
    assert slo_step == 100  # first bad point
    assert drift_step >= 102  # window mean needs >= 3 bad points


def test_monitor_serve_events_replays_both_objectives():
    tr = SpanTracer(trace=("mon",), clock=CountingClock())
    events = []
    for step in range(20):
        tr.emit_span("join", dur=0.0, step=step, component="scheduler.join",
                     wait_steps=0 if step < 10 else 6)
        events.append(ServeStepEvent(
            step=step, op="decode", batch=1, committed=1,
            step_s=0.001 if step < 10 else 0.05))
    events.extend(tr.tracker.events("span"))
    events.sort(key=lambda e: e.step)
    alerts = monitor_serve_events(
        events,
        per_token=SloConfig(target=0.01, window=8, min_points=2),
        join_first_token=SloConfig(target=2.0, window=8, min_points=2),
    )
    objectives = {a.objective for a in alerts}
    assert objectives == {"per_token_latency", "join_to_first_token"}
    assert min(a.step for a in alerts) >= 10


# --------------------------------------------------- planner + fleet hooks
def test_capacity_planner_ingests_slo_alerts():
    from repro.serve.planner import CapacityPlanner

    p = CapacityPlanner()
    a = SloAlertEvent(step=7, slo="svc", objective="latency", target=1.0,
                      burn_rate=3.0, budget=0.05, window_bad=2, window=8)
    n = p.ingest([a, ServeStepEvent(step=8, step_s=0.01, op="decode",
                                    batch=2, committed=2)])
    assert n == 2
    assert p.slo_alerts == [a]
    assert p.last_slo_alert_step == 7


def _constrained_drift_fleet(ticks=90):
    """The 2x-slowdown scenario with a latency breach the autoscaler cannot
    absorb.  The effective-unit autoscaler neutralizes a pure capacity
    halving whenever spare hosts exist (that is its PR-8 contract), and
    exhausting hosts evicts the training job — killing the drift signal —
    so the breach is pinned to slowdown onset with a coincident demand
    spike the replica-capped deployment cannot serve inside its SLO."""
    from repro.fleet.scheduler import FleetConfig
    from repro.fleet.simulate import DEFAULT_FLEET_SLO, FleetSimulator
    from repro.fleet.workloads import (
        RequestTrace,
        ServeDeployment,
        TrainingJob,
        serve_capacity_planner,
        training_model,
    )
    from repro.runtime.chaos import ChaosEvent, ChaosTrace

    tick_s = 300.0
    trace = ChaosTrace.generate(0, ticks, 16, p_straggler=0.0,
                                p_slowdown=0.0, p_preempt=0.0,
                                p_membership=0.0, warmup=4)
    onset = ticks // 3
    trace.events.append(ChaosEvent(step=onset, kind="slowdown", host=-1,
                                   magnitude=2.0, duration=ticks // 3))
    trace.events.sort(key=lambda e: (e.step, e.host, e.kind))
    jobs = [TrainingJob(
        name="job_bg", eps=1e-2, arrival_s=0.0,
        deadline_s=0.70 * ticks * tick_s, m_options=(2, 4, 8),
        model=training_model(compute_s=36.0, rate=3.2e-3),
        ckpt_every_s=6 * tick_s)]
    qps = [2.0] * ticks
    for t in range(onset, min(onset + 6, ticks)):
        qps[t] = 8.0  # > 2-replica capacity: modeled p95 ~3.3s vs 2.2s SLO
    deployments = [ServeDeployment(
        name="serve_pinned",
        planner=serve_capacity_planner(dispatch_s=0.4, per_seq_s=0.35,
                                       log_b_s=0.02),
        trace=RequestTrace(seed=0, tick_s=tick_s, qps=qps),
        slo_p95_s=2.2, gen_tokens=1,
        batch_grid=(1, 2), replica_options=(1, 2))]
    cfg = FleetConfig(
        tick_s=tick_s, spans=True, slo=DEFAULT_FLEET_SLO,
        drift=DriftConfig(window=8, threshold=0.25, min_points=4,
                          cooldown=16))
    sim = FleetSimulator(trace, jobs, deployments, cfg)
    return sim.run(steps=ticks), onset


def test_fleet_slo_alert_precedes_drift_detector():
    log, onset = _constrained_drift_fleet()
    slo_decisions = log.decisions("slo_alert:serve_pinned")
    drift_decisions = log.decisions("drift:job_bg")
    assert slo_decisions, "burn-rate monitor never fired"
    assert drift_decisions, "drift detector never fired"
    assert slo_decisions[0][0] < drift_decisions[0][0]
    assert slo_decisions[0][0] >= onset
    # the alert rides the bus as a typed event too
    alerts = log.events("slo_alert")
    assert alerts and alerts[0].slo == "serve_pinned"
    assert alerts[0].burn_rate >= 2.0


def test_slo_boost_raises_autoscale_headroom():
    """A fired alert grants extra headroom: the same demand provisions one
    more replica while the boost window is open."""
    from repro.fleet.cluster import FleetCluster
    from repro.fleet.scheduler import SLO_BOOST_TICKS, FleetConfig, FleetScheduler
    from repro.fleet.workloads import (
        RequestTrace,
        ServeDeployment,
        serve_capacity_planner,
    )
    from repro.runtime.chaos import ChaosTrace

    def provision(boosted):
        trace = ChaosTrace.generate(0, 4, 12, p_straggler=0.0,
                                    p_slowdown=0.0, p_preempt=0.0,
                                    p_membership=0.0)
        cluster = FleetCluster(trace)
        cluster.advance(0)
        dep = ServeDeployment(
            name="svc",
            planner=serve_capacity_planner(dispatch_s=0.018,
                                           per_seq_s=0.0042, log_b_s=0.002),
            trace=RequestTrace(seed=0, tick_s=300.0, qps=[4.0] * 4),
            slo_p95_s=4.5, gen_tokens=64,
            batch_grid=(1, 2, 4, 8), replica_options=tuple(range(1, 13)))
        sched = FleetScheduler(cluster, [], [dep], FleetConfig(tick_s=300.0))
        if boosted:
            sched._slo_boost_until["svc"] = SLO_BOOST_TICKS
        sched._autoscale_serve(0, 0.0, [])
        return dep.replicas

    assert provision(boosted=True) == provision(boosted=False) + 1


def test_fleet_spans_are_modeled_time_and_deterministic():
    log1, _ = _constrained_drift_fleet(ticks=24)
    log2, _ = _constrained_drift_fleet(ticks=24)
    spans1 = log1.events("span")
    spans2 = log2.events("span")
    assert spans1 and spans1 == spans2
    ticks = [s for s in spans1 if s.component == "fleet.tick"]
    assert len(ticks) == 24
    assert all(t.dur == 300.0 and t.t0 == t.step * 300.0 for t in ticks)
    kids = [s for s in spans1 if s.parent_id]
    tick_ids = {t.span_id for t in ticks}
    assert kids and all(k.parent_id in tick_ids for k in kids)
    # children carry the model's promise next to the modeled measurement
    assert all(k.predicted_s is not None for k in kids)
    serve = [k for k in kids if k.component == "fleet.serve"]
    assert all(s.predicted_s == 2.2 for s in serve)  # the SLO target


def test_fleet_span_and_slo_opt_ins_stay_off_by_default():
    from repro.fleet import run_fleet_sim

    log = run_fleet_sim(0, ticks=12, scenario="drift")
    assert log.events("span") == []
    assert log.events("slo_alert") == []
    assert "spans" not in log.meta and "slo" not in log.meta


def test_fleet_run_with_spans_and_slo_replays_identically():
    from repro.fleet import replay, run_fleet_sim

    log = run_fleet_sim(0, ticks=30, scenario="drift", drift=True,
                        spans=True, slo=True)
    assert log.meta["spans"] and log.meta["slo"]
    again = replay(log)
    assert again.signature() == log.signature()
    assert again.events("span") == log.events("span")
