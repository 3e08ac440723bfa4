"""Re-probes per fleet that the fleet-wide gate granted (``reprobe_grants``
of ``repro.fleet.run``, the ``ReprobeLimiter``'s grants in the run)."""
from benchmarks.chip import program_counters


def read(ctx):
    return program_counters.per_fleet(ctx, "reprobe_grants")
