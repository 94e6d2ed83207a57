#!/usr/bin/env python3
"""Record the small profiler trace that ``test_trace.py`` reads, on a TPU.

    python3 bench/tests/record_trace.py <out_dir>

Traces three calls of the Pallas paged-decode kernel at stablelm widths
(8 rows, 32 heads of 64, 16-token pages) and a bf16 matmul, inside the
``bench.window`` and ``bench.step`` host annotations the benchmark writes,
then copies the ``.xplane.pb`` to ``<out_dir>/paged_decode.xplane.pb`` and
prints each plane and line with a few of its events.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import trace as trace_mod
    from repro.kernels.flash_decode.kernel import paged_flash_decode_pallas

    if jax.default_backend() != "tpu":
        print("record_trace: JAX found no TPU; nothing was run", file=sys.stderr)
        return 2
    b, hk, d, page, npp = 8, 32, 64, 16, 16
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.standard_normal((1 + b * npp, hk, page, d)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((b, hk, 1, d)), jnp.bfloat16)
    lens = jnp.asarray(rng.integers(1, npp * page, b), jnp.int32)
    pt = jnp.asarray(1 + np.arange(b * npp).reshape(b, npp), jnp.int32)
    kernel = jax.jit(lambda q, k, v, n, t: paged_flash_decode_pallas(q, k, v, n, t))
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    mm = jax.jit(lambda a: a @ a)
    kernel(q, pool, pool, lens, pt).block_until_ready()
    mm(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                kernel(q, pool, pool, lens, pt).block_until_ready()
                mm(x).block_until_ready()
    jax.profiler.stop_trace()
    src = trace_mod.find_xplane(tmp)
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out / "paged_decode.xplane.pb")
    shutil.rmtree(tmp, ignore_errors=True)

    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(str(out / "paged_decode.xplane.pb")).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            for e in evs[:6]:
                print(f"    {e.name!r} start {e.start_ns} dur {e.duration_ns} "
                      f"stats {dict(e.stats)}")
    s = trace_mod.summarize(trace_mod.load(str(out / "paged_decode.xplane.pb")))
    print(f"busy {s.busy_s} s of window {s.window_s} s on {s.n_chips} chip(s); "
          f"paged_decode kernel {s.kernel_s('paged_decode')} s")
    print(f"top ops {s.device_ops}")
    print(f"idle gaps {s.idle_gaps}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
