"""Host time per fleet admitting requests (``admit_ns`` of ``repro.fleet.run``):
routing, testbed, environment and sampler set-up of each session (ms)."""
from benchmarks.chip import program_trace


def read(ctx):
    return program_trace.metric(ctx, "admit_ms")
