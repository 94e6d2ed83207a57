"""The generator serves every seed the same cycle of sizes and arrivals."""
from collections import Counter

import numpy as np
import pytest

from bench import spec, traffic

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
