"""Share of the chip's bf16 peak in the decode steps, in %: the FLOPs the
steps must do (active parameters, attention at the actual lengths, the LM
head) over the decode spans' summed time.  Moves itl_p50_ms."""
from bench import costs


def read(run):
    flops = secs = 0.0
    for ev, step in run.step_events("decode"):
        lens = step.decode_lens
        if not lens:
            continue
        flops += costs.decode_flops(run.cfg, lens)
        secs += ev.step_s
    if not secs:
        return None
    return 100.0 * flops / (secs * run.peaks["bf16_flops"])
