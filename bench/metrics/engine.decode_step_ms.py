"""Median decode step in ms (the engine's decode span, host clock, ending
in the device sync of the logits).  Moves itl_p50_ms."""
import numpy as np


def read(run):
    evs = run.step_events("decode")
    if not evs:
        return None
    return float(np.median([ev.step_s for ev, _ in evs])) * 1e3
