"""The one traffic generator: reads a mix's parameters from
``traffic/<mix>.json`` and makes the requests of a run from ``--seed``.

A mix gives the lengths of prompts and answers as clipped lognormals, the
granule prompt lengths are drawn in, and how requests arrive:
``"periodic_poisson"``, open loop at the cell's ``rate_rps``, each request
due at its scheduled time whether or not the server keeps up.

The schedule is one period of ``period`` requests, repeated: their prompt
and answer lengths are the lognormals' ``period`` quantiles (stratified,
so a short period still holds the distribution's spread), and the gaps
between their arrivals are an exponential's ``period`` quantiles, scaled
so the period lasts exactly ``period / rate_rps`` seconds.  How lengths
pair up and the order of the period are drawn once from the mix's
``size_seed``.  ``--seed`` picks the request the schedule starts at and
draws the token ids.  So every seed serves the same cycle of sizes and
arrivals from another point in it, and a window that spans whole periods
holds the same requests on every seed.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class RequestSpec:
    prompt: np.ndarray           # int32 token ids
    max_new_tokens: int
    due_s: float                 # seconds after the traffic starts


def _quantile_lengths(spec: Dict, n: int, granule: int) -> np.ndarray:
    """The n quantiles (i + 1/2) / n of the mix's clipped lognormal."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.clip(spec["median"] * np.exp(spec["sigma"] * z),
                spec["min"], spec["max"])
    return (np.round(x / granule) * granule).astype(np.int64).clip(
        spec["min"], spec["max"])


def period(mix: Dict, geometry: Dict):
    """(prompt lengths, answer lengths, gaps after each) of one period, in
    the schedule's order."""
    n = mix["period"]
    order = np.random.default_rng(mix["size_seed"])
    prompts = _quantile_lengths(mix["prompt"], n, mix.get("granule", 1))
    outputs = _quantile_lengths(mix["output"], n, 1)[order.permutation(n)]
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = gaps / gaps.sum() * n / geometry["rate_rps"]
    perm = order.permutation(n)
    return prompts[perm], outputs[perm], gaps[order.permutation(n)]


def generate(mix: Dict, geometry: Dict, vocab: int, seed: int,
             horizon_s: float) -> List[RequestSpec]:
    if mix["arrivals"] != "periodic_poisson":
        raise ValueError(f"arrivals {mix['arrivals']!r} not in "
                         "('periodic_poisson',)")
    prompts, outputs, gaps = period(mix, geometry)
    n_period = len(prompts)
    rng = np.random.default_rng(seed)
    start = int(rng.integers(n_period))
    n = int(math.ceil(horizon_s * geometry["rate_rps"])) + n_period
    out, due = [], 0.0
    for k in range(n):
        i = (start + k) % n_period
        out.append(RequestSpec(
            prompt=rng.integers(0, vocab, int(prompts[i]), dtype=np.int32),
            max_new_tokens=int(outputs[i]), due_s=due))
        due += float(gaps[i])
    return out
