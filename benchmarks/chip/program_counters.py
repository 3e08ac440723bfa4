"""Counters of the program's ``repro.fleet.run`` spans, per fleet, beyond
the ones ``program_trace`` reduces: a counter is summed over the fleets of
the traced window that carry it and divided by every fleet there.  A trace
where no fleet carries it reads ``None``."""
from __future__ import annotations

from benchmarks.chip import harness, program_trace


def per_fleet(ctx: dict, counter: str) -> float | None:
    if ctx.get("summary") is None:
        return None
    path = program_trace.find_xplane(str(harness.TRACE_DIR))
    if path is None:
        return None
    ev = program_trace.load(path)
    fleets = program_trace.reduce(ev)
    if fleets is None:
        return None
    w0, w1 = ev.window
    values = [float(stats[counter]) for name, s, d, stats in ev.spans
              if name == "fleet.run" and s >= w0 and s + d <= w1
              and counter in stats]
    return sum(values) / fleets.fleets if values else None
