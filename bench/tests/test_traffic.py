"""The generator serves every seed the same cycle of sizes and arrivals;
closed-loop callers keep one request each in flight."""
import time
import zlib
from collections import Counter, defaultdict
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, spec, traffic

CELL = spec.load_cell("stablelm-1.6b.chat")


def window(mix, geometry, seed, t0, seconds):
    specs = traffic.generate(mix, geometry, 1000, seed, t0 + seconds + 5.0)
    return [s for s in specs if t0 <= s.due_s < t0 + seconds]


@pytest.mark.parametrize("seed", [1, 4_000_000_007, 2**31 + 5])
def test_whole_periods_hold_the_same_requests_on_every_seed(seed):
    mix, g = CELL.traffic, CELL.geometry
    prompts, outputs, gaps = traffic.period(mix, g)
    seconds = mix["period"] / g["rate_rps"] * 3
    held = window(mix, g, seed, g["ramp_s"], seconds)
    assert Counter((len(s.prompt), s.max_new_tokens) for s in held) == \
        Counter({(int(p), int(o)): 3 for p, o in zip(prompts, outputs)})
    assert gaps.sum() == pytest.approx(mix["period"] / g["rate_rps"])


def test_seeds_start_the_cycle_at_different_points_and_draw_their_tokens():
    mix, g = CELL.traffic, CELL.geometry
    a = traffic.generate(mix, g, 1000, 11, 60.0)
    b = traffic.generate(mix, g, 1000, 12, 60.0)
    assert [len(s.prompt) for s in a] != [len(s.prompt) for s in b]
    again = traffic.generate(mix, g, 1000, 11, 60.0)
    assert all(np.array_equal(x.prompt, y.prompt) and x.due_s == y.due_s
               for x, y in zip(a, again))


def test_lengths_are_the_lognormal_quantiles_in_granules():
    mix = CELL.traffic
    prompts, outputs, _ = traffic.period(mix, CELL.geometry)
    assert (prompts % mix["granule"] == 0).all()
    assert np.median(prompts) == mix["prompt"]["median"]
    assert np.median(outputs) == mix["output"]["median"]
    assert prompts.min() >= mix["prompt"]["min"]
    assert outputs.max() <= mix["output"]["max"]


@pytest.mark.parametrize("seed", ["4500000001", "2147483653"])
def test_periodic_poisson_schedule_is_the_frozen_one(seed):
    """chat.json's schedule, request for request, as the generator made it
    before closed arrivals were added (``data/chat_schedule.json``)."""
    frozen = spec.load_json(spec.ROOT / "bench" / "tests" / "data" /
                            "chat_schedule.json")
    specs = traffic.generate(CELL.traffic, CELL.geometry, frozen["vocab"],
                             int(seed), frozen["horizon_s"])
    got = [[len(s.prompt), s.max_new_tokens, s.due_s,
            zlib.crc32(s.prompt.tobytes())] for s in specs]
    assert got == frozen["requests"][seed]


class FakeEngine:
    """Serves up to ``slots`` requests at once, one token each a step, in
    the order they came; the rest wait."""

    def __init__(self, slots: int):
        self.slots, self.step_count = slots, 0
        self.waiting, self.running = [], []
        self.scheduler = self

    @property
    def drained(self) -> bool:
        return not self.waiting and not self.running

    def submit(self, prompt, max_new_tokens, arrival_step):
        r = SimpleNamespace(generated=[], n=max_new_tokens)
        self.waiting.append(r)
        return r

    def step(self):
        while self.waiting and len(self.running) < self.slots:
            self.running.append(self.waiting.pop(0))
        for r in self.running:
            r.generated.append(0)
        self.running = [r for r in self.running if len(r.generated) < r.n]
        self.step_count += 1
        time.sleep(0.0005)


def closed_run(callers=5, ramp_s=0.05, seconds=0.6, seed=3):
    mix = dict(CELL.traffic, arrivals="closed")
    g = {"callers": callers, "ramp_s": ramp_s}
    loop = harness.ClosedLoop(FakeEngine(slots=3),
                              traffic.requests(mix, g, 1000, seed),
                              time.perf_counter(), callers, ramp_s)
    loop.run_until(loop.t_start + seconds)
    return loop, mix, g


def test_closed_callers_keep_one_request_in_flight_and_send_on_finish():
    loop, _, _ = closed_run()
    by_caller = defaultdict(list)
    for r in loop.live:
        by_caller[r.caller].append(r)
    assert sorted(by_caller) == list(range(5))
    for caller, recs in by_caller.items():
        recs.sort(key=lambda r: r.sent)
        assert recs[0].due == pytest.approx(loop.t_start + caller * 0.05 / 5,
                                            abs=1e-12)
        assert len(recs) >= 3  # each caller served several requests
        for prev, nxt in zip(recs, recs[1:]):
            # the next one falls due when the previous one finished ...
            assert nxt.due == prev.finished
            # ... so no two of a caller's requests are ever in flight at once
            assert nxt.sent >= prev.finished
    # only the requests still in flight are unfinished, one a caller at most
    open_callers = [r.caller for r in loop.live if r.finished == float("inf")]
    assert len(open_callers) == len(set(open_callers))


def test_closed_callers_send_the_period_sizes_in_order():
    loop, mix, g = closed_run()
    prompts, outputs, gaps = traffic.period(mix, g)
    assert gaps is None
    sent = sorted(loop.live, key=lambda r: r.sent)
    start = int(np.random.default_rng(3).integers(len(prompts)))  # the seed's
    for k, r in enumerate(sent):
        i = (start + k) % len(prompts)
        assert (len(r.spec.prompt), r.spec.max_new_tokens) == \
            (prompts[i], outputs[i])
