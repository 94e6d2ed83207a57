"""Shape-keyed persisted config cache for the kernel autotuner.

A cache entry maps one ``(family, shape, dtype, backend)`` key to the
block config the sweep harness measured fastest, plus the measurement
itself.  Keys are flat strings::

    flash_decode_paged|b4_d64_g2_hk4_npp128_page16|float32|cpu

— family, underscore-joined ``<name><value>`` shape items in sorted key
order, jnp dtype name, and ``jax.default_backend()``.  The value side
keeps the original shape dict so consumers (telemetry export, capacity
planning) never parse the signature back.

Persistence is a single JSON file (the ``path`` argument; the sweep CLI
writes ``results/tune_cache.json`` unless told otherwise), written
atomically (tmp + rename).  ``path=None`` keeps the cache in memory only.
Kernel-geometry lookups read a file only when ``$REPRO_TUNE_CACHE`` names
one, so what the program compiles follows from tracked code by default.
"""

from __future__ import annotations

import json
import os
from pathlib import Path
from typing import Dict, Optional

import jax

from repro.telemetry.io import atomic_write_json, file_lock

DEFAULT_CACHE_PATH = "results/tune_cache.json"
_SCHEMA_VERSION = 1


def dtype_name(dtype) -> str:
    import jax.numpy as jnp

    return jnp.dtype(dtype).name


def backend_name() -> str:
    return jax.default_backend()


def shape_sig(shape: Dict[str, int]) -> str:
    return "_".join(f"{k}{int(v)}" for k, v in sorted(shape.items()))


def cache_key(family: str, shape: Dict[str, int], dtype, backend: Optional[str] = None) -> str:
    return "|".join([family, shape_sig(shape), dtype_name(dtype), backend or backend_name()])


class ConfigCache:
    def __init__(self, path: Optional[str] = None, tracker=None):
        self.path = path
        self.entries: Dict[str, Dict] = {}
        self.sweeps = 0  # incremented by the sweep harness, not persisted
        # optional repro.telemetry.Tracker; the sweep harness emits a
        # TuneEvent here (falls back to the process default tracker)
        self.tracker = tracker
        if path is not None and Path(path).exists():
            self.load()

    @classmethod
    def default_path(cls) -> Optional[str]:
        """The file kernel-geometry lookups read: ``$REPRO_TUNE_CACHE``, or
        none (in memory, so every kernel takes its tracked default)."""
        return os.environ.get("REPRO_TUNE_CACHE")

    # ------------------------------------------------------------------
    def get(self, key: str) -> Optional[Dict]:
        return self.entries.get(key)

    def config(self, key: str) -> Optional[Dict]:
        entry = self.entries.get(key)
        return None if entry is None else entry["config"]

    def put(
        self,
        key: str,
        *,
        family: str,
        shape: Dict[str, int],
        dtype,
        config: Dict,
        us_per_call: float,
        swept: int,
        pruned: int,
        backend: Optional[str] = None,
    ) -> Dict:
        entry = {
            "family": family,
            "shape": {k: int(v) for k, v in shape.items()},
            "dtype": dtype_name(dtype),
            "backend": backend or backend_name(),
            "config": {k: int(v) for k, v in config.items()},
            "us_per_call": float(us_per_call),
            "candidates_swept": int(swept),
            "candidates_pruned": int(pruned),
        }
        self.entries[key] = entry
        return entry

    # ------------------------------------------------------------------
    def load(self) -> "ConfigCache":
        with open(self.path) as f:
            payload = json.load(f)
        if payload.get("version") != _SCHEMA_VERSION:
            # stale schema: start fresh rather than misread configs
            self.entries = {}
            return self
        self.entries = payload["entries"]
        return self

    def save(self) -> None:
        """Merge-then-write through the shared atomic helper.

        Two processes sweeping different keys against the same file (the
        CI slow job overlapping tier-1) used to race: last writer wins,
        silently dropping the other's entries.  Now each save takes an
        exclusive lock, re-reads the on-disk entries, and overlays its
        own before the atomic replace, so concurrent sweeps union
        instead of clobbering."""
        if self.path is None:
            return
        with file_lock(str(self.path) + ".lock"):
            if Path(self.path).exists():
                try:
                    with open(self.path) as f:
                        payload = json.load(f)
                    if payload.get("version") == _SCHEMA_VERSION:
                        self.entries = {**payload["entries"], **self.entries}
                except (OSError, json.JSONDecodeError):
                    pass  # torn/unreadable: our atomic write supersedes it
            atomic_write_json(
                self.path, {"version": _SCHEMA_VERSION, "entries": self.entries}
            )
