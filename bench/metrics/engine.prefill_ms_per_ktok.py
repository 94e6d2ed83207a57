"""Prefill-chunk span time per 1,000 prompt tokens prefilled, in ms, over
the window's chunks.  Moves ttft_p80_ms."""


def read(run):
    evs = run.step_events("prefill")
    tokens = sum(ev.prefill_tokens for ev, _ in evs)
    if not tokens:
        return None
    return sum(ev.step_s for ev, _ in evs) / tokens * 1e6
