"""Device idle time inside engine steps, in ms per step: over the traced
window, the time no XLA op ran on the chip while an ``engine.step`` span
was open, over the number of engine.step spans that start in the window.
Notes the idle ms per step under each innermost open engine span, the
idle time outside any step, and each span's median.  Moves itl_p50_ms."""
from bench import program_spans


def read(run):
    phases = program_spans.of_run(run)
    if phases is None:
        return None
    n = phases.n_steps
    inside = phases.step_idle_ns
    outside = phases.idle_ns.get(program_spans.OUTSIDE, 0.0)
    own = phases.idle_ns.get(program_spans.STEP, 0.0)
    parts = sorted(((ns, name) for name, ns in phases.idle_ns.items()
                    if name != program_spans.OUTSIDE), reverse=True)
    run.note(f"engine spans: {n} steps in the traced window; device idle "
             f"{inside / 1e6:.3f} ms inside engine.step "
             f"({100 * inside / max(inside + outside, 1):.1f}% of the window's "
             f"idle; {100 * (inside - own) / max(inside, 1):.1f}% of that under a "
             f"child span), {outside / 1e6:.3f} ms outside; idle ms per step: "
             + ", ".join(f"{name} {ns / n / 1e6:.3f}" for ns, name in parts))
    run.note("engine spans: median ms: " + ", ".join(
        f"{name} {phases.median_ms(name):.3f}" for name in sorted(phases.durations)))
    return inside / n / 1e6
