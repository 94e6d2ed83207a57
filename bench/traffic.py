"""The one traffic generator: reads a mix's parameters from
``traffic/<mix>.json`` and makes the requests of a run from ``--seed``.

A mix gives the lengths of prompts and answers as clipped lognormals, the
granule prompt lengths are drawn in, and how requests arrive:

- ``"periodic_poisson"``: open loop at the cell's ``rate_rps``, each
  request due at its scheduled time whether or not the server keeps up
  (``generate``);
- ``"closed"``: the cell's ``callers``, each sending its next request when
  its last one finished, with no think time; their first requests fall due
  spread evenly over the cell's ``ramp_s``.  The serving loop
  (``harness.ClosedLoop``) sets each due time; ``requests`` gives the
  sizes and tokens in order.

The schedule is one period of ``period`` requests, repeated: their prompt
and answer lengths are the lognormals' ``period`` quantiles (stratified,
so a short period still holds the distribution's spread), and the gaps
between their arrivals are an exponential's ``period`` quantiles, scaled
so the period lasts exactly ``period / rate_rps`` seconds.  How lengths
pair up and the order of the period are drawn once from the mix's
``size_seed``.  ``--seed`` picks the request the schedule starts at and
draws the token ids.  So every seed serves the same cycle of sizes and
arrivals from another point in it, and a window that spans whole periods
holds the same requests on every seed.  A closed mix takes its sizes from
the same period in the same order; the callers set the arrivals.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, Iterator, List

import numpy as np


@dataclasses.dataclass
class RequestSpec:
    prompt: np.ndarray           # int32 token ids
    max_new_tokens: int
    due_s: float                 # seconds after the traffic starts (open loop)


def _quantile_lengths(spec: Dict, n: int, granule: int) -> np.ndarray:
    """The n quantiles (i + 1/2) / n of the mix's clipped lognormal."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.clip(spec["median"] * np.exp(spec["sigma"] * z),
                spec["min"], spec["max"])
    return (np.round(x / granule) * granule).astype(np.int64).clip(
        spec["min"], spec["max"])


ARRIVALS = ("periodic_poisson", "closed")


def period(mix: Dict, geometry: Dict):
    """(prompt lengths, answer lengths, gaps after each) of one period, in
    the schedule's order; the gaps are None for a closed mix."""
    if mix["arrivals"] not in ARRIVALS:
        raise ValueError(f"arrivals {mix['arrivals']!r} not in {ARRIVALS}")
    n = mix["period"]
    order = np.random.default_rng(mix["size_seed"])
    prompts = _quantile_lengths(mix["prompt"], n, mix.get("granule", 1))
    outputs = _quantile_lengths(mix["output"], n, 1)[order.permutation(n)]
    perm = order.permutation(n)
    if mix["arrivals"] == "closed":
        return prompts[perm], outputs[perm], None
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = gaps / gaps.sum() * n / geometry["rate_rps"]
    return prompts[perm], outputs[perm], gaps[order.permutation(n)]


def _schedule(prompts, outputs, vocab: int, seed: int) -> Iterator:
    """(period index, prompt, answer length) of each request in order, the
    period repeated without end from the point ``seed`` picks."""
    rng = np.random.default_rng(seed)
    n_period = len(prompts)
    start = int(rng.integers(n_period))
    k = 0
    while True:
        i = (start + k) % n_period
        yield i, rng.integers(0, vocab, int(prompts[i]), dtype=np.int32), \
            int(outputs[i])
        k += 1


def generate(mix: Dict, geometry: Dict, vocab: int, seed: int,
             horizon_s: float) -> List[RequestSpec]:
    """An open-loop mix's requests due within ``horizon_s`` and one period
    more, each with its due time."""
    if mix["arrivals"] != "periodic_poisson":
        raise ValueError(f"arrivals {mix['arrivals']!r} has no schedule of "
                         "due times; a closed mix is served by its callers")
    prompts, outputs, gaps = period(mix, geometry)
    n = int(math.ceil(horizon_s * geometry["rate_rps"])) + len(prompts)
    out, due = [], 0.0
    for _, (i, prompt, new) in zip(range(n), _schedule(prompts, outputs,
                                                       vocab, seed)):
        out.append(RequestSpec(prompt=prompt, max_new_tokens=new, due_s=due))
        due += float(gaps[i])
    return out


def requests(mix: Dict, geometry: Dict, vocab: int,
             seed: int) -> Iterator[RequestSpec]:
    """A closed mix's requests in the order its callers send them, without
    end; the serving loop sets each one's due time."""
    if mix["arrivals"] != "closed":
        raise ValueError(f"arrivals {mix['arrivals']!r} is not 'closed'")
    prompts, outputs, _ = period(mix, geometry)
    for _, prompt, new in _schedule(prompts, outputs, vocab, seed):
        yield RequestSpec(prompt=prompt, max_new_tokens=new, due_s=math.nan)
