"""Persistent XLA compilation cache for the entry points.

JAX keys its on-disk cache by program, compile options and device, and the
directory's path is part of what makes a later run find an entry again.  So
the cache lives where ``$JAX_COMPILATION_CACHE_DIR`` says when that is set
(JAX reads the variable itself), and otherwise at one fixed directory in the
checkout, ``.jax_cache/`` (git-ignored) — never a temporary, per-process or
per-run path.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
