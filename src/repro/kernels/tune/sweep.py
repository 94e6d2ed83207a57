"""Sweep harness: measure surviving candidates, persist the winner.

``sweep`` builds a real invocation of the kernel family at the requested
shape, times every candidate config that survives roofline pruning
(``tune.roofline``), and records the fastest in the config cache.
``ensure`` is the memoized entry point: a cache hit returns immediately
without re-sweeping (asserted by tests via ``ConfigCache.sweeps``).

On CPU the harness times the jnp implementations (and interpret-mode
Pallas where that is the only implementation) — a proxy with honest
relative ordering for blocking/looping overheads; on a TPU backend the
same harness times the real kernels, and entries are keyed by backend so
the two never mix.
"""

from __future__ import annotations

import functools
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.tune import roofline
from repro.kernels.tune.cache import ConfigCache, cache_key
from repro.telemetry import TuneEvent, default_tracker

FAMILIES = (
    "flash_attention",
    "flash_decode",
    "flash_decode_paged",
    "prefill_chunk",
    "ssm_scan",
    "sdca",
)

# default sweep shapes: "full" targets serving-scale caches, "smoke" keeps
# the CI sweep to tens of milliseconds
SWEEP_SHAPES: Dict[str, Dict[str, Dict[str, int]]] = {
    "full": {
        "flash_attention": {"b": 1, "h": 8, "s": 1024, "d": 64},
        "flash_decode": {"b": 4, "h": 8, "s": 512, "d": 64},
        "flash_decode_paged": {"b": 4, "hk": 4, "g": 2, "d": 64, "page": 16, "npp": 128},
        "prefill_chunk": {"p": 512, "hk": 4, "g": 2, "d": 64, "page": 16, "npp": 64},
        "ssm_scan": {"bt": 2, "s": 512, "dn": 64, "n": 16},
        "sdca": {"m": 4, "nl": 256, "d": 64, "h": 256},
    },
    "smoke": {
        "flash_attention": {"b": 1, "h": 2, "s": 64, "d": 16},
        "flash_decode": {"b": 2, "h": 2, "s": 64, "d": 16},
        "flash_decode_paged": {"b": 2, "hk": 2, "g": 2, "d": 16, "page": 8, "npp": 8},
        "prefill_chunk": {"p": 32, "hk": 2, "g": 2, "d": 16, "page": 8, "npp": 8},
        "ssm_scan": {"bt": 1, "s": 64, "dn": 8, "n": 4},
        "sdca": {"m": 2, "nl": 32, "d": 16, "h": 32},
    },
}


def time_fn(fn: Callable, *args, iters: int = 5) -> float:
    """Wall-clock microseconds per call (one warmup invocation, then the
    mean of ``iters`` timed calls)."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def _pow2_range(lo: int, hi: int) -> List[int]:
    out, v = [], lo
    while v <= hi:
        out.append(v)
        v *= 2
    return out


def ragged_lengths(b: int, capacity: int) -> np.ndarray:
    """Deterministic serving-like fill: longest sequence at half capacity,
    the rest tapering off — the operating point the engine actually runs
    at mid-trace."""
    return np.asarray([max(1, (capacity * (b - i)) // (2 * b)) for i in range(b)], np.int32)


def candidates_for(family: str, shape: Dict[str, int]) -> List[Dict[str, int]]:
    if family == "flash_attention":
        s = shape["s"]
        blocks = [v for v in _pow2_range(16, 512) if v <= max(s, 16)]
        return [{"block_q": bq, "block_k": bk} for bq in blocks for bk in blocks]
    if family == "flash_decode":
        s = shape["s"]
        return [{"block_k": bk} for bk in _pow2_range(16, 1024) if bk <= max(s, 16)]
    if family == "flash_decode_paged":
        npp = shape["npp"]
        return [{"pages_per_program": p} for p in _pow2_range(1, 128) if p <= npp]
    if family == "prefill_chunk":
        p = shape["p"]
        return [{"chunk": c} for c in _pow2_range(16, 512) if c <= max(p, 16)]
    if family == "ssm_scan":
        s = shape["s"]
        return [{"chunk": c} for c in _pow2_range(16, 256) if c <= max(s, 16)]
    if family == "sdca":
        return [{"use_pallas": 0}, {"use_pallas": 1}]
    raise ValueError(f"unknown kernel family {family!r}")


# ---------------------------------------------------------------------------
# Per-family measurable cases
# ---------------------------------------------------------------------------
def _case_flash_attention(shape, dtype):
    from repro.kernels.flash_attention.ops import flash_attention

    b, h, s, d = shape["b"], shape["h"], shape["s"], shape["d"]
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, h, s, d), dtype)
    v = jax.random.normal(ks[2], (b, h, s, d), dtype)

    def build(config):
        return jax.jit(functools.partial(flash_attention, causal=True, **config)), (q, k, v)

    return build


def _case_flash_decode(shape, dtype):
    from repro.kernels.flash_decode.kernel import flash_decode_pallas

    b, h, s, d = shape["b"], shape["h"], shape["s"], shape["d"]
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, h, d), dtype)
    kc = jax.random.normal(ks[1], (b, h, s, d), dtype)
    vc = jax.random.normal(ks[2], (b, h, s, d), dtype)
    lens = jnp.asarray(ragged_lengths(b, s))

    def build(config):
        fn = jax.jit(functools.partial(flash_decode_pallas, **config))
        return fn, (q, kc, vc, lens)

    return build


def _case_flash_decode_paged(shape, dtype):
    from repro.kernels.flash_decode.ops import paged_decode_attention

    b, hk, g, d = shape["b"], shape["hk"], shape["g"], shape["d"]
    page, npp = shape["page"], shape["npp"]
    n_pages = b * npp + 1
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(b, hk * g, d), dtype)
    kp = jnp.asarray(rng.randn(n_pages, hk, page, d), dtype)
    vp = jnp.asarray(rng.randn(n_pages, hk, page, d), dtype)
    rows = [rng.choice(n_pages - 1, npp, replace=False) + 1 for _ in range(b)]
    pt = jnp.asarray(np.stack(rows), jnp.int32)
    lens = jnp.asarray(ragged_lengths(b, npp * page))
    impl = "pallas" if jax.default_backend() == "tpu" else "stream"

    def build(config):
        part = functools.partial(
            paged_decode_attention, impl=impl, pages_per_program=config["pages_per_program"]
        )
        return jax.jit(part), (q, kp, vp, lens, pt)

    return build


def _case_prefill_chunk(shape, dtype):
    """Whole-prompt chunked prefill at chunk width C: ceil(p/C) calls of the
    paged-prefill flash path (scatter chunk K/V, gather the page row, attend
    with static q_offset).  Small chunks pay repeated page-row gathers and
    dispatch; large chunks pay step latency — the tunable is that knee.  The
    timed fn drives every chunk so candidates are compared on full-prompt
    cost, not per-call cost."""
    from repro.kernels.flash_decode.ops import paged_prefill_attention

    p, hk, g, d = shape["p"], shape["hk"], shape["g"], shape["d"]
    page, npp = shape["page"], shape["npp"]
    n_pages = npp + 1
    rng = np.random.RandomState(5)
    kp = jnp.asarray(rng.randn(n_pages, hk, page, d), dtype)
    vp = jnp.asarray(rng.randn(n_pages, hk, page, d), dtype)
    pt = jnp.asarray(rng.permutation(npp)[None] + 1, jnp.int32)

    def build(config):
        c = config["chunk"]
        calls = []
        for i in range(-(-p // c)):
            s0 = i * c
            q = jnp.asarray(rng.randn(1, hk * g, c, d), dtype)
            lens = jnp.asarray([min(s0 + c, p)], jnp.int32)
            fn = jax.jit(functools.partial(paged_prefill_attention, q_offset=s0))
            calls.append((fn, q, lens))

        def run(kp_, vp_, pt_):
            out = None
            for fn, q, lens in calls:
                out = fn(q, kp_, vp_, lens, pt_)
            return out

        return run, (kp, vp, pt)

    return build


def _case_ssm_scan(shape, dtype):
    from repro.kernels.ssm_scan.ops import selective_scan

    bt, s, dn, n = shape["bt"], shape["s"], shape["dn"], shape["n"]
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (bt, s, dn), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bt, s, dn), dtype))
    A = -jnp.abs(jax.random.normal(ks[2], (dn, n))) - 0.1
    B = jax.random.normal(ks[3], (bt, s, n), dtype)
    C = jax.random.normal(ks[4], (bt, s, n), dtype)
    D = jnp.full((dn,), 0.4)

    def build(config):
        return jax.jit(lambda *a: selective_scan(*a, chunk=config["chunk"])[0]), (x, dt, A, B, C, D)

    return build


def _case_sdca(shape, dtype):
    from repro.kernels.sdca.ops import local_sdca

    m, nl, d, h = shape["m"], shape["nl"], shape["d"], shape["h"]
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    X = jax.random.normal(ks[0], (m, nl, d), dtype)
    y = jnp.sign(jax.random.normal(ks[1], (m, nl), dtype))
    a = jnp.zeros((m, nl), dtype)
    w = jnp.zeros((d,), dtype)
    idx = jnp.stack([jax.random.permutation(k, nl)[:h] for k in jax.random.split(ks[2], m)])

    def build(config):
        use_pallas = bool(config["use_pallas"])

        def run(*args):
            return local_sdca(*args, 1.0, 1e-3, float(m * nl), use_pallas=use_pallas)

        return jax.jit(run), (X, y, a, w, idx)

    return build


_CASES = {
    "flash_attention": _case_flash_attention,
    "flash_decode": _case_flash_decode,
    "flash_decode_paged": _case_flash_decode_paged,
    "prefill_chunk": _case_prefill_chunk,
    "ssm_scan": _case_ssm_scan,
    "sdca": _case_sdca,
}


# ---------------------------------------------------------------------------
# Sweep + memoized entry point
# ---------------------------------------------------------------------------
def sweep(
    family: str,
    shape: Dict[str, int],
    dtype=jnp.float32,
    *,
    cache: Optional[ConfigCache] = None,
    iters: int = 5,
    slack: float = roofline.PRUNE_SLACK,
) -> Tuple[Dict[str, int], Dict]:
    """Measure the pruned candidate set; store and return the winner."""
    if cache is None:
        from repro.kernels.tune import default_cache

        cache = default_cache()
    cache.sweeps += 1
    build = _CASES[family](shape, dtype)
    kept, n_pruned = roofline.prune(family, shape, candidates_for(family, shape), slack=slack)
    results = []
    for est in kept:
        fn, args = build(est.config)
        results.append((time_fn(fn, *args, iters=iters), est.config))
    best_us, best_config = min(results, key=lambda r: r[0])
    key = cache_key(family, shape, dtype)
    entry = cache.put(
        key,
        family=family,
        shape=shape,
        dtype=dtype,
        config=best_config,
        us_per_call=best_us,
        swept=len(kept),
        pruned=n_pruned,
    )
    cache.save()
    # every sweep result rides the bus: a cache with its own tracker keeps
    # the events alongside the entries, otherwise the process-wide default
    tracker = getattr(cache, "tracker", None) or default_tracker()
    tracker.emit(TuneEvent.from_legacy_row(entry))
    return best_config, entry


def ensure(
    family: str,
    shape: Dict[str, int],
    dtype=jnp.float32,
    *,
    cache: Optional[ConfigCache] = None,
    sweep_on_miss: bool = True,
    **sweep_kwargs,
) -> Optional[Dict]:
    """Cached config for the key, sweeping at most once per (shape, dtype,
    backend).  Returns None on a miss when ``sweep_on_miss=False``."""
    if cache is None:
        from repro.kernels.tune import default_cache

        cache = default_cache()
    config = cache.config(cache_key(family, shape, dtype))
    if config is not None:
        return config
    if not sweep_on_miss:
        return None
    config, _ = sweep(family, shape, dtype, cache=cache, **sweep_kwargs)
    return config


def sweep_all(
    preset: str = "smoke",
    *,
    families: Sequence[str] = FAMILIES,
    dtype=jnp.float32,
    cache: Optional[ConfigCache] = None,
    iters: int = 5,
) -> List[Dict]:
    """Sweep every family at its preset shape; returns the cache entries."""
    entries = []
    for family in families:
        shape = SWEEP_SHAPES[preset][family]
        _, entry = sweep(family, shape, dtype, cache=cache, iters=iters)
        entries.append(entry)
    return entries
