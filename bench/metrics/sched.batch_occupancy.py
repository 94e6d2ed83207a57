"""Decode rows over max_batch, mean over the window's decode steps, in %.
Under open-loop arrivals at a fixed rate a request that is served faster
holds its row for less time, so occupancy falls with the token gap.
Moves itl_p50_ms."""


def read(run):
    evs = run.step_events("decode")
    if not evs:
        return None
    return 100.0 * sum(ev.batch for ev, _ in evs) / (
        len(evs) * run.geometry["max_batch"])
