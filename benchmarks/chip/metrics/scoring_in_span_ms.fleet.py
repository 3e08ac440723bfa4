"""Device time per fleet of the programs that ran inside ``repro.fleet.score``
spans (ms): scoring found by the program's span, not by program names."""
from benchmarks.chip import program_trace


def read(ctx):
    return program_trace.metric(ctx, "scoring_in_span_ms")
