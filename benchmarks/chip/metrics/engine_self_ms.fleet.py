"""Host time per fleet in the engine's own work (ms): ``repro.fleet.run`` less
scoring, admission and session steps; the heap, dispatch, finish
bookkeeping and the report."""
from benchmarks.chip import program_trace


def read(ctx):
    return program_trace.metric(ctx, "engine_self_ms")
