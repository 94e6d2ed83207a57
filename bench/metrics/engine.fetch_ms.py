"""Median ``engine.decode.fetch`` span in ms (the copy of a decode step's
logits to the host, after the device finished), over the decode steps in
the traced window, from the program's spans in the profiler trace.  Moves
itl_p50_ms."""
from bench import program_spans


def read(run):
    phases = program_spans.of_run(run)
    return None if phases is None else phases.median_ms("engine.decode.fetch")
