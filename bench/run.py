#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Set-up (timed from process start as ``setup_s``): weights made on the
device from ``--seed``, the engine built at the cell's geometry, every
program the cell's traffic can reach compiled or loaded from the
persistent cache, and a ramp of real traffic until the load is steady.  Then ``--seconds`` of measured window.
``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (spans on, the profiler on for the window's last
seconds).  Either way the run ends by checking what the window served
against the plain reference (``check.py``) and prints one JSON line.

Exits 2 with no result when JAX finds no TPU or fewer chips than the cell
asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import spec  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"
TRACE_SECONDS = 5.0  # profiled tail of the window in a --trace 1 run


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    cell = spec.load_cell(args.workload)
    import jax

    devices = jax.devices()
    if jax.default_backend() != "tpu" or len(devices) < cell.chips:
        log(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
            f"found backend {jax.default_backend()!r} with {len(devices)} "
            "device(s); nothing was run")
        return 2
    from bench.harness import run_cell

    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), devices=devices[:cell.chips],
                      t_process=T_PROCESS, trace_dir=TRACE_DIR,
                      trace_seconds=TRACE_SECONDS)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    gc.collect()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
