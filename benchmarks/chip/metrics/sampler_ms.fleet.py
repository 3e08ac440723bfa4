"""Host time per fleet in the sampler's own decisions (ms): the session steps
(``step_ns``) less the simulated network inside them (``netsim_ns``)."""
from benchmarks.chip import program_trace


def read(ctx):
    return program_trace.metric(ctx, "sampler_ms")
