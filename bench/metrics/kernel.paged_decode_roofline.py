"""Share of its roofline in the Pallas paged-decode kernel, in %: the least
time of the calls (K/V bytes at the actual lengths, 4*B*H*L*hd FLOPs; the
larger of the two bounds) over the kernel's device time in the traced
window.  The kernel is the Pallas call (``tpu_custom_call``) inside the
decode step's program, its only Pallas kernel.  Counts the decode steps
whose host span lies inside the traced window, and every kernel event the
trace holds, so edge steps can only lower it.  Moves itl_p50_ms."""
from bench import costs

MODULE = "jit_decode_step_paged"


def read(run):
    if run.trace is None:
        return None
    kernel_s = run.trace.kernel_s(MODULE)
    if not kernel_s:
        run.note(f"paged decode kernel: no Pallas call in module {MODULE} in "
                 "the trace; kernel.paged_decode_roofline left out")
        return None
    least = 0.0
    bounds = set()
    for ev, step in run.step_events("decode"):
        if step.t0 < run.trace_t0:
            continue
        lens = step.decode_lens
        if not lens:
            continue
        s, bound = costs.least_seconds(*costs.paged_decode_kernel(run.cfg, lens),
                                       run.peaks)
        least += costs.n_attention_layers(run.cfg) * s
        bounds.add(bound)
    if not least:
        return None
    run.note(f"paged decode kernel: {kernel_s:.6f} s in the trace, "
             f"{' and '.join(sorted(bounds))} bound")
    return 100.0 * least / kernel_s
