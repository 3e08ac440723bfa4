"""Host time per fleet inside ``repro.fleet.score``: routing every request
and scoring each cluster on the device up to the host's read (ms)."""
from benchmarks.chip import program_trace


def read(ctx):
    return program_trace.metric(ctx, "scoring_wall_ms")
