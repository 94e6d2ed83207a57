"""Scheduler queue wait, p80 in ms: from when a request was due to the
start of its first prefill span, over requests whose prefill started in
the window.  Moves ttft_p80_ms."""
import numpy as np


def read(run):
    first = {}
    for ev, t in run.spans("prefill_chunk") + run.spans("prefill"):
        rid = ev.attrs.get("rid")
        first[rid] = min(first.get(rid, t), t)
    waits = []
    for rec in run.loop.live:
        t = first.get(rec.request.rid)
        if t is not None and run.w0 <= t < run.w1:
            waits.append(t - rec.due)
    if not waits:
        return None
    return float(np.percentile(waits, 80)) * 1e3
