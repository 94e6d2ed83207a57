#!/usr/bin/env python3
"""Find an open-loop cell's knee once, on the chip: the highest arrival
rate the system sustains with no growing backlog.

    python3 bench/sweep.py --workload <cell> --rates 1 2 3 --seconds 20

One process builds and warms the cell once, then serves the cell's
traffic mix at each rate for ``--seconds`` (after the cell's ramp) and
drains.  Per rate it prints the completed tokens/s, TTFT p50/p90 in the
first and the last third of the window, and the backlog (requests due
but not yet given a first token) at the window's start and end.  A rate
holds when the backlog does not grow across the window and the last
third's TTFT p90 is within 1.5x (plus 0.5 s) of the first third's; the
last line names the knee, the highest rate below the first that did not
hold (rates in increasing order).
The cell's ``rate_rps`` is set by hand from this, at about 0.8 of the
knee; the benchmark's runs never search for a rate.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import spec, traffic  # noqa: E402


def backlog(loop, t: float) -> int:
    return sum(1 for r in loop.live if r.due <= t and not (r.times and r.times[0] <= t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--ramp", type=float, default=None,
                    help="seconds of traffic before each window (default: "
                         "the cell's ramp_s)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--smoke", action="store_true",
                    help="rehearse at smoke widths on any backend")
    args = ap.parse_args(argv)

    import jax

    from bench.harness import Loop, build, pct, use_compile_cache, warm

    if jax.default_backend() != "tpu" and not args.smoke:
        print("sweep: JAX found no TPU; nothing was run", file=sys.stderr)
        return 2
    cell = spec.load_cell(args.workload)
    use_compile_cache(cell.root)
    cfg = spec.arch_config(cell.config, args.smoke)
    engine, _ = build(cell, cfg, args.seed, jax.devices()[0], False)
    warm(engine, cell, args.seed)
    knee = rates_seen = 0  # rates held so far, counted from the lowest
    for rate in args.rates:
        g = dict(cell.geometry, rate_rps=rate)
        ramp = g["ramp_s"] if args.ramp is None else args.ramp
        specs = traffic.generate(cell.traffic, g, cfg.vocab_size, args.seed,
                                 ramp + args.seconds + 5.0)
        t0 = time.perf_counter()
        loop = Loop(engine, specs, t0)
        w0, w1 = t0 + ramp, t0 + ramp + args.seconds
        loop.run_until(w1)
        third = args.seconds / 3
        parts = []
        for lo in (w0, w1 - third):
            ttft = [r.times[0] - r.due for r in loop.live
                    if r.times and lo <= r.times[0] < lo + third]
            parts.append(f"{pct(ttft, 50) * 1e3:.0f}/{pct(ttft, 90) * 1e3:.0f}"
                         if ttft else "-")
        tokens = sum(1 for r in loop.live for t in r.times if w0 <= t < w1)
        p90 = [pct([r.times[0] - r.due for r in loop.live
                    if r.times and lo <= r.times[0] < lo + third] or [0.0], 90)
               for lo in (w0, w1 - third)]
        held = backlog(loop, w1) <= max(2, backlog(loop, w0)) and \
            p90[1] <= 1.5 * p90[0] + 0.5
        if held and knee == rates_seen:
            knee = rates_seen + 1
        rates_seen += 1
        print(f"sweep {args.workload} rate {rate:g}/s: {tokens / args.seconds:.1f} "
              f"tokens/s; ttft p50/p90 ms first third {parts[0]}, last third "
              f"{parts[1]}; backlog at start {backlog(loop, w0)}, at end "
              f"{backlog(loop, w1)}", flush=True)
        # drain before the next rate
        loop.specs = loop.specs[:loop.next]
        while loop.busy():
            loop.step()
    # the knee: the highest rate below the first that did not hold
    print(f"sweep {args.workload} knee "
          f"{args.rates[knee - 1] if knee else 0.0}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
