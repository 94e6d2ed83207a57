"""repro.kernels.tune — shape-keyed Pallas/jnp kernel autotuner.

A sweep harness plus a persisted config cache covering every kernel
family (flash_attention, flash_decode + flash_decode_paged, prefill_chunk,
ssm_scan, sdca).  Keys are (family, shape, dtype, backend); values are the
measured fastest block configs.  See DESIGN.md §10.

Public surface:

* ``ensure(family, shape, dtype)`` — cached config, sweeping at most once
  per key (the memoization the acceptance test asserts).
* ``lookup(family, shape, dtype)`` — cheap read-only cache hit for the
  ``tuned=True`` paths in the ops wrappers; never sweeps, returns None on
  a miss (callers fall back to their defaults).  Safe under jit tracing.
* ``default_cache()`` — process-wide cache bound to ``$REPRO_TUNE_CACHE``
  when set, else in memory (untracked files never steer a compile).
* ``tune_events`` / ``bench_rows`` — telemetry export: typed bus events
  for ``CapacityPlanner.ingest``/dryrun system-model fitting, bench rows
  for the perf-gate trajectory (``decode_step_rows`` is the deprecated
  dict form).

CLI: ``python -m repro.kernels.tune --preset smoke``.
"""

from __future__ import annotations

from typing import Dict, Optional

from repro.kernels.tune.cache import (
    ConfigCache,
    cache_key,
    shape_sig,
)
from repro.kernels.tune.sweep import (
    FAMILIES,
    SWEEP_SHAPES,
    candidates_for,
    ensure,
    ragged_lengths,
    sweep,
    sweep_all,
    time_fn,
)
from repro.kernels.tune.telemetry import bench_rows, decode_step_rows, tune_events

__all__ = [
    "ConfigCache",
    "FAMILIES",
    "SWEEP_SHAPES",
    "bench_rows",
    "cache_key",
    "candidates_for",
    "decode_step_rows",
    "default_cache",
    "ensure",
    "lookup",
    "ragged_lengths",
    "reset_default_cache",
    "shape_sig",
    "sweep",
    "sweep_all",
    "time_fn",
    "tune_events",
]

_default_cache: Optional[ConfigCache] = None


def default_cache() -> ConfigCache:
    """Process-wide cache, loaded lazily from ``ConfigCache.default_path``."""
    global _default_cache
    if _default_cache is None:
        _default_cache = ConfigCache(ConfigCache.default_path())
    return _default_cache


def reset_default_cache() -> None:
    """Drop the singleton (tests repoint ``$REPRO_TUNE_CACHE``)."""
    global _default_cache
    _default_cache = None


def lookup(family: str, shape: Dict[str, int], dtype) -> Optional[Dict]:
    """Read-only config lookup against the default cache; None on miss."""
    return default_cache().config(cache_key(family, shape, dtype))
