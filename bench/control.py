#!/usr/bin/env python3
"""Readings that set a cell's ``gap_limit``: the program's served gap and
the control's, on many seeds in one process.

    python3 bench/control.py --workload <cell> --seconds 10 --seeds 1 2 3 ...

Each seed is a whole run of the cell (weights, warm-up from the
persistent cache, the cell's own traffic and load for ``--seconds``);
after it the served sample is read twice in the float32 reference: the
gap of the served tokens (the lower reading) and the gap of the tokens the
fp8 reference puts first at the same positions (the control's reading,
which has to come out above the limit).  Prints one line per seed and the
largest program reading and smallest control reading.  The benchmark's
own runs never run this.  ``--smoke`` reads them at smoke widths on any
backend (``tests/test_control.py``).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import check, spec  # noqa: E402


def readings(cell, seeds, seconds: float, devices, smoke: bool = False):
    """[(seed, program gap, control gap)] for each seed."""
    import jax

    from bench.harness import run_cell
    from bench.weights import make_weights

    out = []

    def judge(cfg, seed, served, geometry, device):
        w = make_weights(cfg, seed, device)
        with jax.default_matmul_precision("highest"):
            prog, ctrl = check.control_gaps(w, cfg, served, geometry["max_seq"])
        out.append((seed, prog, ctrl))
        return check.Verdict(prog <= geometry["gap_limit"], {
            "served_gap": {"value": prog, "limit": geometry["gap_limit"]}})

    for seed in seeds:
        result = run_cell(cell, seed=seed, seconds=seconds, trace=False,
                          devices=devices, t_process=time.perf_counter(),
                          trace_dir=ROOT / ".bench_trace", trace_seconds=0.0,
                          smoke=smoke, judge=judge)
        s, prog, ctrl = out[-1]
        print(f"control {cell.name} seed {s}: program gap {prog!r} control "
              f"gap {ctrl!r}; run {json.dumps(result)}", flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    import jax

    cell = spec.load_cell(args.workload)
    if jax.default_backend() != "tpu" and not args.smoke:
        print("control: JAX found no TPU; nothing was run", file=sys.stderr)
        return 2
    r = readings(cell, args.seeds, args.seconds, jax.devices()[:cell.chips],
                 args.smoke)
    print(f"control {cell.name}: largest program gap {max(p for _, p, _ in r)!r}, "
          f"smallest control gap {min(c for _, _, c in r)!r} over {len(r)} seeds",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
