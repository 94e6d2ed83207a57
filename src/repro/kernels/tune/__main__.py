"""Autotune CLI: sweep kernel families, persist the config cache.

  PYTHONPATH=src python -m repro.kernels.tune --preset smoke
  PYTHONPATH=src python -m repro.kernels.tune --preset full \
      --families flash_decode_paged --cache results/tune_cache.json

Prints one line per swept family (winner config, measured us, pruning
stats) and, with ``--telemetry``, the exported benchmark rows.
"""

from __future__ import annotations

import argparse

import jax.numpy as jnp

from repro.kernels.tune import (
    FAMILIES,
    ConfigCache,
    bench_rows,
    sweep_all,
)
from repro.kernels.tune.cache import DEFAULT_CACHE_PATH


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=["smoke", "full"], default="smoke")
    ap.add_argument("--families", nargs="+", default=list(FAMILIES), choices=list(FAMILIES))
    ap.add_argument(
        "--cache",
        default=ConfigCache.default_path() or DEFAULT_CACHE_PATH,
        help="config-cache JSON path (default: $REPRO_TUNE_CACHE or results/tune_cache.json)",
    )
    ap.add_argument("--dtype", default="float32", choices=["float32", "bfloat16"])
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument(
        "--telemetry", action="store_true", help="also print the exported benchmark rows"
    )
    args = ap.parse_args()

    cache = ConfigCache(args.cache)
    dtype = jnp.dtype(args.dtype)
    entries = sweep_all(
        args.preset, families=args.families, dtype=dtype, cache=cache, iters=args.iters
    )
    for e in entries:
        cfg = ";".join(f"{k}={v}" for k, v in sorted(e["config"].items()))
        print(
            f"[tuned] {e['family']:20s} {cfg:24s} "
            f"{e['us_per_call']:10.1f} us  "
            f"(swept {e['candidates_swept']}, "
            f"pruned {e['candidates_pruned']}, {e['backend']})"
        )
    print(f"# cache: {args.cache} ({len(cache.entries)} entries)")
    if args.telemetry:
        for name, us, derived in bench_rows(cache):
            print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
