"""Host time per fleet reading the link's shared external load (``load_ns``
of ``repro.fleet.run``): each chunk's ``current_load`` on a load that
varies, inside the simulated network's time (ms).  A constant load is not
timed and reads 0."""
from benchmarks.chip import program_counters


def read(ctx):
    ns = program_counters.per_fleet(ctx, "load_ns")
    return None if ns is None else ns * 1e-6
