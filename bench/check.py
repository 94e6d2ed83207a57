"""Decide ``correct``: what the timed window served against the reference.

After the window, a sample of the requests it finished (drawn from the
seed, with the request that served the most tokens always in it) is run
once through the float32 reference over its prompt and served tokens.  At
every served position the reading is how far the served token's reference
logit lies below the reference's best, relative to the row's largest
|logit|; the widest over the sample (``served_gap``) is held to the cell's
``gap_limit``.  A served token that the reference also puts first reads 0.

The control (``control_gaps``) reads the same positions with the fp8
reference put in the program's place: the gap of the token the fp8
reference puts first.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from typing import Dict, List, Sequence, Tuple

import numpy as np


@dataclasses.dataclass
class Served:
    prompt: np.ndarray
    tokens: np.ndarray   # every token served, in order


@dataclasses.dataclass
class Verdict:
    correct: bool
    compared: Dict[str, Dict[str, float]]


def sample(finished, seed: int, k: int) -> List[Served]:
    """k finished requests: the one with the most served tokens, and k-1
    more drawn from the seed."""
    if not finished:
        return []
    longest = max(range(len(finished)), key=lambda i: len(finished[i].generated))
    rest = [i for i in range(len(finished)) if i != longest]
    rng = np.random.default_rng(seed)
    pick = [longest] + list(rng.permutation(rest)[: max(k - 1, 0)])
    return [Served(np.asarray(finished[i].spec.prompt, np.int32),
                   np.asarray(finished[i].generated, np.int32)) for i in pick]


def _reference_rows(w, cfg, s: Served, pad_to: int, precision: str):
    """Reference logits at the positions that predict each served token."""
    import jax.numpy as jnp

    from bench.reference import forward

    seq = np.concatenate([s.prompt, s.tokens[:-1]])
    if len(seq) > pad_to:
        raise ValueError(f"sequence of {len(seq)} > {pad_to}")
    padded = np.zeros(pad_to, np.int32)
    padded[: len(seq)] = seq
    p = len(s.prompt)
    logits = forward(w, jnp.asarray(padded), cfg, precision)
    return np.asarray(logits[p - 1: p - 1 + len(s.tokens)], np.float32)


def row_gaps(ref: np.ndarray, picks: np.ndarray) -> np.ndarray:
    """(best - ref[pick]) / max|ref| per row."""
    best = ref.max(axis=1)
    chosen = ref[np.arange(len(picks)), picks]
    return (best - chosen) / np.abs(ref).max(axis=1)


def served_gap(w, cfg, served: Sequence[Served], pad_to: int) -> Tuple[float, int]:
    """(widest gap, tokens compared) of the served tokens."""
    widest, n = 0.0, 0
    for s in served:
        ref = _reference_rows(w, cfg, s, pad_to, "f32")
        if not np.isfinite(ref).all():
            return float("inf"), n
        widest = max(widest, float(row_gaps(ref, s.tokens).max()))
        n += len(s.tokens)
    return widest, n


def control_gaps(w, cfg, served: Sequence[Served], pad_to: int) -> Tuple[float, float]:
    """(served gap, control gap): the widest gap of the served tokens and
    of the tokens the fp8 reference puts first, both read in the float32
    reference."""
    served_w, control_w = 0.0, 0.0
    for s in served:
        ref = _reference_rows(w, cfg, s, pad_to, "f32")
        low = _reference_rows(w, cfg, s, pad_to, "fp8")
        served_w = max(served_w, float(row_gaps(ref, s.tokens).max()))
        control_w = max(control_w, float(row_gaps(ref, low.argmax(axis=1)).max()))
    return served_w, control_w


def run(cfg, seed: int, served: Sequence[Served], geometry: Dict, device) -> Verdict:
    """Rebuild the weights from the seed and judge the sample."""
    import jax

    from bench.weights import make_weights

    limit = geometry["gap_limit"]
    checked = {"requests_checked": {"value": len(served), "limit": 1}}
    if not served:
        return Verdict(False, checked)
    t0 = time.perf_counter()
    w = make_weights(cfg, seed, device)
    with jax.default_matmul_precision("highest"):
        gap, n = served_gap(w, cfg, served, geometry["max_seq"])
    del w
    print(f"bench: reference compared {n} served tokens of {len(served)} "
          f"requests in {time.perf_counter() - t0:.1f} s", file=sys.stderr,
          flush=True)
    return Verdict(gap <= limit, {
        "served_gap": {"value": gap, "limit": limit}, **checked})
