"""Share of the chip's HBM bandwidth in the decode steps of an MoE model,
in %: the least bytes the steps must move (``costs/moe.py``: bf16 weights
with the held experts the rows route to, K/V at the actual lengths) over
the decode spans' summed time.  Moves itl_p50_ms."""
from bench.costs import moe as moe_costs


def read(run):
    nbytes = secs = 0.0
    for ev, step in run.step_events("decode"):
        lens = step.decode_lens
        if not lens:
            continue
        nbytes += moe_costs.decode_bytes(run.cfg, lens)
        secs += ev.step_s
    if not secs:
        return None
    return 100.0 * nbytes / (secs * run.peaks["hbm_bytes_s"])
