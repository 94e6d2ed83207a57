"""Share of the chip's HBM bandwidth in the decode steps, in %: the least
bytes the steps must move (bf16 weights, K/V at the actual lengths) over
the decode spans' summed time.  Moves itl_p50_ms."""
from bench import costs


def read(run):
    nbytes = secs = 0.0
    for ev, step in run.step_events("decode"):
        lens = step.decode_lens
        if not lens:
            continue
        nbytes += costs.decode_bytes(run.cfg, lens)
        secs += ev.step_s
    if not secs:
        return None
    return 100.0 * nbytes / (secs * run.peaks["hbm_bytes_s"])
