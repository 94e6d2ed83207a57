#!/usr/bin/env python3
"""Rehearse cells without the chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [cell ...] [--seconds 3] [--aot]

For each cell (all of ``BENCHMARK.json`` by default) this runs the cell's
whole code path (weights, warm-up, traffic, window, per-layer readers
except the device trace, the reference check) at the repo's smoke widths
on the CPU, and prints no metric line.  ``--aot`` instead compiles the
cell's decode step and a prefill chunk at full size for a described TPU
v5e and prints their ``memory_analysis()``; nothing runs there.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import spec  # noqa: E402


def smoke(name: str, seconds: float, seed: int) -> None:
    import jax

    from bench.harness import run_cell

    cell = spec.load_cell(name)
    for trace in (False, True):
        t = time.perf_counter()
        out = run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                       devices=jax.devices()[:1], t_process=t,
                       trace_dir=ROOT / ".bench_trace", trace_seconds=1.0,
                       smoke=True)
        names = sorted(out["metrics"])
        print(f"rehearsal {name} trace={int(trace)}: correct={out['correct']} "
              f"attempted={out['attempted']} metrics={names} "
              f"compared={json.dumps(out['compared'])}", flush=True)
        # at smoke widths the cell's limits do not apply: read, not judged


def aot(name: str) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    import repro.kernels.flash_decode.kernel as paged_kernel
    from repro.models.model import LM
    from repro.serve import ServeEngine
    from repro.serve.cache import init_paged_cache, max_pages_per_seq

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    paged_kernel.pallas_interpret = lambda interpret=None: False
    cell = spec.load_cell(name)
    g = cell.geometry
    cfg = spec.arch_config(cell.config)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    lm = LM(cfg, ServeEngine.default_runtime(g["page_size"], paged_impl="pallas"))
    npp = max_pages_per_seq(g["max_seq"], g["page_size"])
    n_pages = g.get("num_pages") or 1 + g["max_batch"] * npp
    place = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)
    params = place(lm.param_shapes())
    cache = place(jax.eval_shape(lambda: init_paged_cache(
        lm, num_pages=n_pages, page_size=g["page_size"], max_batch=g["max_batch"])))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    b, c = g["max_batch"], g["prefill_chunk"]
    steps = {
        "decode": jax.jit(lm.decode_step_paged, donate_argnums=(3,)).lower(
            params, i32(b), i32(b), cache, i32(b, npp)),
        "prefill_chunk": jax.jit(lm.prefill_chunk, static_argnames=("s0",),
                                 donate_argnums=(3,)).lower(
            params, i32(1, c), i32(), cache, i32(1, npp),
            s0=cell.traffic["prompt"]["max"] - c),
    }
    gb = 1e9
    for step, lowered in steps.items():
        t = time.perf_counter()
        m = lowered.compile().memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 - m.alias_size_in_bytes + m.temp_size_in_bytes)
        print(f"aot {name} {step}: args {m.argument_size_in_bytes / gb:.3f} GB, "
              f"temp {m.temp_size_in_bytes / gb:.3f} GB, total {total / gb:.3f} GB "
              f"({n_pages} pages; compiled in {time.perf_counter() - t:.1f} s)",
              flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seed", type=int, default=5_000_000_017)
    ap.add_argument("--aot", action="store_true")
    args = ap.parse_args(argv)
    names = args.cells or [w["name"] for w in
                           spec.load_json(spec.ROOT / "BENCHMARK.json")["workloads"]]
    for name in names:
        if args.aot:
            aot(name)
        else:
            smoke(name, args.seconds, args.seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
