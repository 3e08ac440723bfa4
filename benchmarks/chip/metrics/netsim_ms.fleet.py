"""Host time per fleet in the simulated network, ``TenantEnvironment.transfer``
(``netsim_ns`` of ``repro.fleet.run``) (ms)."""
from benchmarks.chip import program_trace


def read(ctx):
    return program_trace.metric(ctx, "netsim_ms")
