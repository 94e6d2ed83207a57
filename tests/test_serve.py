"""Serve subsystem: allocator/scheduler invariants, paged-decode equivalence,
prefix-reuse exactness, and the CapacityPlanner fit/query round-trip.

The allocator is covered by property-based tests (random alloc/share/free
schedules against a shadow refcount model) rather than hand-picked edge
cases — the invariants hold under ANY schedule, so that is what we test."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.hemingway import NoFeasiblePlan
from repro.serve import CapacityPlanner, OutOfPages, PagePool, ServeEngine
from repro.serve.paging import SCRATCH_PAGE

ARCH = "qwen3-14b"  # dense: slot-independent decode (see engine docstring)
GEOM = dict(smoke=True, max_batch=2, page_size=8, max_seq=64, seed=0)


def _prompt(rng, n):
    return rng.randint(0, 256, n).astype(np.int32)


# ---------------------------------------------------------------- allocator
@settings(max_examples=60, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(2, 24))
def test_page_pool_random_schedule_invariants(seed, num_pages):
    """Under a random alloc/share/free schedule the pool matches a shadow
    refcount model exactly: conservation (free + in-use = capacity), no
    scratch handout, OutOfPages exactly when the free list is short, and
    zero leaked pages once every reference is dropped."""
    rng = np.random.RandomState(seed)
    pool = PagePool(num_pages=num_pages, page_size=8)
    shadow = {}  # page -> refcount (live pages only)
    for _ in range(200):
        op = rng.choice(["alloc", "share", "free"])
        live = [p for p, c in shadow.items() if c > 0]
        if op == "alloc":
            n = int(rng.randint(1, max(num_pages // 2, 2)))
            if n > pool.free_pages:
                with pytest.raises(OutOfPages):
                    pool.alloc(n)
            else:
                got = pool.alloc(n)
                assert len(got) == n == len(set(got))
                assert SCRATCH_PAGE not in got
                assert not any(p in live for p in got), "handed out live page"
                for p in got:
                    shadow[p] = 1
        elif op == "share" and live:
            take = [p for p in live if rng.rand() < 0.3] or [live[0]]
            pool.share(take)
            for p in take:
                shadow[p] += 1
        elif op == "free" and live:
            take = [p for p in live if rng.rand() < 0.4] or [live[0]]
            pool.free(take)
            for p in take:
                shadow[p] -= 1
        # invariants after every operation
        in_use = sum(1 for c in shadow.values() if c > 0)
        assert pool.pages_in_use == in_use
        assert pool.free_pages + in_use == num_pages - 1  # scratch pinned
        for p, c in shadow.items():
            assert pool.refcount(p) == c
    # drain every remaining reference -> no leaks
    for p, c in list(shadow.items()):
        if c > 0:
            pool.free([p] * c)
    assert pool.pages_in_use == 0
    assert pool.free_pages == num_pages - 1


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_page_pool_rejects_invalid_ops(seed):
    """Double free, freeing/sharing the scratch page, and sharing dead
    pages are errors under any state the pool can reach."""
    rng = np.random.RandomState(seed)
    pool = PagePool(num_pages=int(rng.randint(3, 12)), page_size=8)
    pages = pool.alloc(int(rng.randint(1, pool.free_pages + 1)))
    pool.free(pages)
    with pytest.raises(ValueError):
        pool.free(pages[:1])          # double free
    with pytest.raises(ValueError):
        pool.share(pages[:1])         # share after death
    with pytest.raises(ValueError):
        pool.free([SCRATCH_PAGE])     # scratch is pinned
    with pytest.raises(ValueError):
        pool.share([SCRATCH_PAGE])


# ---------------------------------------------------------------- scheduler
def test_no_page_leak_after_evict():
    eng = ServeEngine(ARCH, **GEOM)
    rng = np.random.RandomState(0)
    for i in range(5):  # more requests than slots -> queueing + eviction
        eng.submit(_prompt(rng, 9 + 3 * i), max_new_tokens=3,
                   arrival_step=i % 2)
    eng.run()
    assert eng.scheduler.drained
    # prefix cache still pins published pages; clearing it must leave zero
    eng.prefix.clear(eng.pool)
    assert eng.pool.pages_in_use == 0
    assert eng.pool.free_pages == eng.pool.num_pages - 1
    # idle slots all point at the scratch page with zero length
    assert (eng.page_tables == SCRATCH_PAGE).all()
    assert (eng.lengths == 0).all()


def test_join_on_arrival_preserves_decoded_tokens():
    rng = np.random.RandomState(1)
    prompt = _prompt(rng, 16)
    guest = _prompt(rng, 9)

    solo = ServeEngine(ARCH, **GEOM)
    r_solo = solo.submit(prompt, max_new_tokens=8)
    solo.run()

    busy = ServeEngine(ARCH, **GEOM)
    r_host = busy.submit(prompt, max_new_tokens=8)
    r_guest = busy.submit(guest, max_new_tokens=4, arrival_step=3)
    busy.run()

    assert r_guest.admitted_step >= 3, "guest must join mid-decode"
    assert r_host.generated == r_solo.generated
    assert len(r_guest.generated) == 4


def test_evict_on_finish_frees_slot_for_queued_request():
    eng = ServeEngine(ARCH, **GEOM)
    rng = np.random.RandomState(2)
    first = [eng.submit(_prompt(rng, 10), max_new_tokens=2) for _ in range(2)]
    third = eng.submit(_prompt(rng, 10), max_new_tokens=2)  # no free slot
    eng.run()
    assert all(r.finished_step >= 0 for r in first + [third])
    assert third.admitted_step > first[0].admitted_step


# ------------------------------------------------------------- prefix reuse
def test_prefix_reuse_bit_identical_logits():
    rng = np.random.RandomState(3)
    head = _prompt(rng, 16)  # two full pages of 8
    pA = np.concatenate([head, _prompt(rng, 5)])
    pB = np.concatenate([head, _prompt(rng, 7)])

    cold = ServeEngine(ARCH, collect_logits=True, **GEOM)
    rB_cold = cold.submit(pB, max_new_tokens=5)
    cold.run()

    warm = ServeEngine(ARCH, collect_logits=True, **GEOM)
    warm.submit(pA, max_new_tokens=5)
    warm.run()
    rB = warm.submit(pB, max_new_tokens=5)
    warm.run()

    assert rB.n_shared_pages == 2, "prompt head pages must be shared"
    assert rB.generated == rB_cold.generated
    assert len(rB.logits_trace) == len(rB_cold.logits_trace) == 5
    for got, want in zip(rB.logits_trace, rB_cold.logits_trace):
        np.testing.assert_array_equal(got, want)


def test_prefix_share_join_does_not_perturb_running_donor():
    """A prefix-sharing request joining mid-decode must neither disturb the
    donor's remaining tokens nor lose its own cold-prefill exactness: shared
    pages are never rewritten, and their content is bitwise what the
    joiner's own prefill computed (engine pins the flash block size)."""
    rng = np.random.RandomState(8)
    head = _prompt(rng, 16)
    pA = np.concatenate([head, _prompt(rng, 6)])
    pB = np.concatenate([head, _prompt(rng, 11)])

    solo = ServeEngine(ARCH, collect_logits=True, **GEOM)
    rA_solo = solo.submit(pA, max_new_tokens=10)
    solo.run()
    cold = ServeEngine(ARCH, collect_logits=True, **GEOM)
    rB_cold = cold.submit(pB, max_new_tokens=4)
    cold.run()

    eng = ServeEngine(ARCH, collect_logits=True, **GEOM)
    rA = eng.submit(pA, max_new_tokens=10)
    rB = eng.submit(pB, max_new_tokens=4, arrival_step=3)  # A still decoding
    eng.run()

    assert rB.n_shared_pages == 2 and rB.admitted_step >= 3
    assert rA.generated == rA_solo.generated, "donor perturbed by joiner"
    assert rB.generated == rB_cold.generated
    for got, want in zip(rB.logits_trace, rB_cold.logits_trace):
        np.testing.assert_array_equal(got, want)


def test_full_prompt_reuse_skips_prefill():
    rng = np.random.RandomState(4)
    prompt = _prompt(rng, 16)  # page-aligned
    eng = ServeEngine(ARCH, collect_logits=True, **GEOM)
    r1 = eng.submit(prompt, max_new_tokens=4)
    eng.run()
    r2 = eng.submit(prompt, max_new_tokens=4)
    eng.run()
    assert not r1.prefill_skipped and r2.prefill_skipped
    assert r1.generated == r2.generated
    for got, want in zip(r2.logits_trace, r1.logits_trace):
        np.testing.assert_array_equal(got, want)


def test_full_prompt_reuse_with_mamba_state():
    rng = np.random.RandomState(5)
    prompt = _prompt(rng, 16)
    eng = ServeEngine("falcon-mamba-7b", collect_logits=True, **GEOM)
    r1 = eng.submit(prompt, max_new_tokens=4)
    eng.run()
    r2 = eng.submit(prompt, max_new_tokens=4)
    eng.run()
    assert r2.prefill_skipped
    assert r1.generated == r2.generated
    for got, want in zip(r2.logits_trace, r1.logits_trace):
        np.testing.assert_array_equal(got, want)


# --------------------------------------------------------- step phase spans
def _serve_chunked(trace: bool):
    from repro.telemetry.trace import CountingClock

    eng = ServeEngine(ARCH, prefill_chunk=4, trace=trace,
                      trace_clock=CountingClock(), **GEOM)
    rng = np.random.RandomState(5)
    reqs = [eng.submit(_prompt(rng, 11), max_new_tokens=3),
            eng.submit(_prompt(rng, 6), max_new_tokens=6)]
    eng.run()
    return eng, [r.generated for r in reqs]


def test_engine_step_phases_are_child_spans_in_order():
    eng, _ = _serve_chunked(trace=True)
    spans = eng.events("span")

    def kids(parent):
        inside = [e for e in spans if e.parent_id == parent.span_id]
        for e in inside:
            assert parent.t0 <= e.t0 and e.t0 + e.dur <= parent.t0 + parent.dur
        return sorted(inside, key=lambda e: e.t0)

    steps = [e for e in spans if e.component == "engine.step"]
    assert all(not e.parent_id for e in steps)
    # the last step decodes the one request left and retires it
    last = kids(max(steps, key=lambda e: e.step))
    assert [e.component for e in last] == [
        "engine.schedule", "engine.decode", "engine.sample", "engine.retire"]
    assert [e.component for e in kids(last[1])] == [
        "engine.decode.launch", "engine.decode.wait", "engine.decode.fetch"]
    # a prompt's last chunk hands its first token over in engine.activate
    phases = {e.step: [k.component for k in kids(e)] for e in steps}
    assert any(p[:3] == ["engine.schedule", "engine.prefill_chunk",
                         "engine.activate"] for p in phases.values())
    assert not {"pages.alloc", "pages.evict"} & {e.component for e in spans}


def test_engine_without_tracing_emits_no_span_and_same_tokens():
    off, tokens_off = _serve_chunked(trace=False)
    on, tokens_on = _serve_chunked(trace=True)
    assert off.spans is None and not off.events("span") and on.events("span")
    assert tokens_off == tokens_on
    assert [(e.step, e.op, e.batch) for e in off.events("serve_step")] == [
        (e.step, e.op, e.batch) for e in on.events("serve_step")]
    np.testing.assert_array_equal(off.page_tables, on.page_tables)
    np.testing.assert_array_equal(off.lengths, on.lengths)


# ------------------------------------------------------- MoE work counters
MOE_HELD = 4  # of the smoke model's 8 routed experts


class _HeldMoEEngine(ServeEngine):
    """The smoke DeepSeekMoE with a share of its routed experts held."""

    @staticmethod
    def config_for(arch, smoke):
        import dataclasses

        cfg = ServeEngine.config_for(arch, smoke)
        return dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, held_experts=MOE_HELD))


def _serve_moe(trace: bool):
    eng = _HeldMoEEngine("deepseek-moe-16b", prefill_chunk=4, trace=trace,
                         **GEOM)
    rng = np.random.RandomState(7)
    eng.submit(_prompt(rng, 11), max_new_tokens=3)
    eng.submit(_prompt(rng, 6), max_new_tokens=5)
    eng.run()
    return eng


def test_traced_moe_engine_counts_routed_and_computed_expert_rows():
    eng = _serve_moe(trace=True)
    n_moe = eng.cfg.n_periods  # one MoE layer a period after the dense head
    rows = {"prefill": eng.prefill_chunk, "decode": eng.max_batch}
    steps = eng.events("serve_step")
    assert {e.op for e in steps} == {"prefill", "decode"}
    for e in steps:
        # dropless: each held expert's buffer holds every row of the dispatch
        assert e.expert_rows == MOE_HELD * rows[e.op] * n_moe
        assert 0 <= e.routed_rows <= e.expert_rows
    assert sum(e.routed_rows for e in steps) > 0
    spans = {e.component: e for e in eng.events("span")
             if e.component in ("engine.decode", "engine.prefill_chunk")}
    for comp, op in (("engine.decode", "decode"),
                     ("engine.prefill_chunk", "prefill")):
        assert spans[comp].attrs["expert_rows"] == MOE_HELD * rows[op] * n_moe
        assert 0 <= spans[comp].attrs["routed_rows"] <= \
            spans[comp].attrs["expert_rows"]


def test_untraced_moe_engine_fetches_no_counts():
    eng = _serve_moe(trace=False)
    assert all(e.routed_rows == e.expert_rows == 0
               for e in eng.events("serve_step"))


def test_only_moe_serving_steps_have_the_counts_output():
    import jax

    def n_outputs(eng):
        b = eng.max_batch
        out = jax.eval_shape(
            eng.lm.decode_step_paged, eng.params, np.zeros(b, np.int32),
            np.zeros(b, np.int32), eng.cache, eng.page_tables)
        chunk = jax.eval_shape(
            lambda *a: eng.lm.prefill_chunk(*a, s0=0), eng.params,
            np.zeros((1, 4), np.int32), np.int32(4), eng.cache,
            eng.page_tables[:1])
        return len(out), len(chunk)

    assert n_outputs(ServeEngine(ARCH, **GEOM)) == (2, 2)
    assert n_outputs(_HeldMoEEngine("deepseek-moe-16b", **GEOM)) == (3, 3)


def test_serve_step_rows_without_counts_still_parse_and_legacy_is_unchanged():
    from repro.telemetry import ServeStepEvent, from_dict

    old = {"kind": "serve_step", "v": 1, "step": 3, "step_s": 0.5,
           "op": "decode", "batch": 2, "committed": 2}
    ev = from_dict(old)
    assert (ev.routed_rows, ev.expert_rows) == (0, 0)
    counted = ServeStepEvent(step=3, step_s=0.5, op="decode", batch=2,
                             committed=2, routed_rows=5, expert_rows=40)
    assert counted.to_legacy() == ev.to_legacy() == {
        "step": 3, "batch": 2, "step_s": 0.5, "kind": "decode", "committed": 2}
    assert from_dict(counted.to_dict()) == counted


# ---------------------------------------------------------- capacity planner
def test_capacity_planner_fit_query_roundtrip():
    # synthetic telemetry from a known affine step model t(b) = a + c*b
    a, c = 0.02, 0.005
    planner = CapacityPlanner()
    for b in [1, 2, 4, 8] * 4:
        planner.observe(b, a + c * b)
    planner.fit()
    for b in (1, 4, 16):
        assert planner.step_time(b) == pytest.approx(a + c * b, rel=0.05)

    # min-fleet query: 10-token responses, p50 target admits b <= 8.
    # capacity per replica at b=8 is 8/0.06 = 133 tok/s = 13.3 qps, so
    # 45 qps needs m=4 (b=4 offers only 40 qps at m=4).
    plan = planner.plan(target_p50_s=0.61, qps=45.0,
                        gen_tokens=10, batch_grid=[1, 2, 4, 8],
                        m_grid=[1, 2, 4, 8, 16, 32])
    assert plan.m == 4 and plan.algorithm == "continuous@b8"
    assert plan.predicted_time == pytest.approx(10 * (a + c * 8), rel=0.05)

    # budget query: fixed fleet, lowest feasible latency (b=1 suffices)
    best = planner.best_latency_within_fleet(
        m=4, qps=10.0, gen_tokens=10, batch_grid=[1, 2, 4, 8])
    assert best.predicted_time == pytest.approx(10 * (a + c * 1), rel=0.05)

    no_plan = planner.plan(target_p50_s=1e-6, qps=40.0, gen_tokens=10,
                           batch_grid=[1, 2], m_grid=[1])
    assert isinstance(no_plan, NoFeasiblePlan) and not no_plan
    assert no_plan.query == "capacity_plan"
    assert no_plan.table, "infeasible result still carries its predictions"

    no_fleet = planner.best_latency_within_fleet(
        m=1, qps=1e6, gen_tokens=10, batch_grid=[1, 2])
    assert isinstance(no_fleet, NoFeasiblePlan)
    assert "cannot sustain" in no_fleet.reason


def test_capacity_planner_from_engine_telemetry():
    eng = ServeEngine(ARCH, **GEOM)
    rng = np.random.RandomState(7)
    eng.submit(_prompt(rng, 10), max_new_tokens=6)
    eng.submit(_prompt(rng, 13), max_new_tokens=4, arrival_step=1)
    eng.run()
    planner = CapacityPlanner()
    planner.observe_telemetry(eng.telemetry)
    planner.fit()  # distinct batch sizes 1 and 2 observed
    assert planner.step_time(1) > 0
    assert planner.tokens_per_s(2, m=2) > planner.tokens_per_s(2, m=1) * 1.5
