"""Routed (token, expert) pairs over the expert rows the MoE FFN computed,
in %, summed over the window's decode and prefill-chunk steps (the
engine's ``routed_rows`` and ``expert_rows`` counters, which it fetches
when tracing).  A dropless buffer of every dispatched row for each held
expert reads k / E.  Moves itl_p50_ms.  A program without the counters
reads nothing."""


def read(run):
    routed = rows = 0
    for op in ("decode", "prefill"):
        for ev, _ in run.step_events(op):
            routed += getattr(ev, "routed_rows", 0)
            rows += getattr(ev, "expert_rows", 0)
    if not rows:
        return None
    return 100.0 * routed / rows
