"""Events per fleet that the event loop processed (``events`` of
``repro.fleet.run``)."""
from benchmarks.chip import program_trace


def read(ctx):
    return program_trace.metric(ctx, "events")
