"""Host time per fleet outside ``run_fleet`` (ms): the traced window less the
``repro.fleet.run`` spans, the benchmark's own drawing and flattening."""
from benchmarks.chip import program_trace


def read(ctx):
    return program_trace.metric(ctx, "outside_run_ms")
