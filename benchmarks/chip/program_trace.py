"""The program's own spans and counters in a traced window, per fleet.

``repro.obs`` writes ``repro.*`` spans to the profiler trace's host plane,
with its counters as each event's stats: ``repro.fleet.run`` around each
``run_fleet`` call (stats ``events``, ``admissions``, ``admit_ns``,
``step_ns``, ``netsim_ns``), and ``repro.fleet.score`` around admission
scoring inside it.  Two steps, kept apart so the second can be checked on
hand-made events:

* :func:`load` reads the trace once per file (the metric readers share
  it): the ``repro.*`` host events with their stats, the harness's
  ``bench.window`` span, and each TPU device's ``XLA Modules`` intervals;
* :func:`reduce` divides the window among the fleets run inside it.

"Per fleet" divides by the number of ``repro.fleet.run`` spans inside the
window.  The six times partition the window: scoring (wall), admission,
sampler, simulated network, the engine's own bookkeeping, and the time
outside ``run_fleet`` (the benchmark's own host work) add up to the window
over the fleets.  Device scoring is the union of the device's programs
inside the ``repro.fleet.score`` spans: found by the program's span, not by
the programs' names.  A trace of a program without these spans reduces to
``None``, and every reader then returns ``None``.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import os

from benchmarks.chip import harness, trace
from benchmarks.chip.clock import SPAN_PREFIX

PROGRAM_PREFIX = "repro."
COUNTERS = ("events", "admissions", "admit_ns", "step_ns", "netsim_ns")


@dataclasses.dataclass
class ProgramEvents:
    """Events read from one trace; times in nanoseconds."""

    spans: list[tuple[str, float, float, dict]]  # repro.* (name, s, d, stats)
    window: tuple[float, float] | None  # the harness's ``window`` span
    programs: list[list[tuple[float, float]]]  # per device: (start, dur)


@dataclasses.dataclass
class Fleets:
    """The traced window divided among its fleets (totals in ns)."""

    fleets: int
    window_ns: float
    run_ns: float  # union of the fleets' ``run_fleet`` spans
    score_ns: float  # ``repro.fleet.score`` spans
    score_device_ns: float  # device programs inside the score spans
    counters: dict[str, float]  # summed over the fleets that carry each

    def per_fleet_ms(self, ns: float) -> float:
        return ns * 1e-6 / self.fleets

    def metrics(self) -> dict[str, float]:
        """Every metric this module's readers report; the counters' are
        left out where no fleet carries them."""
        c = self.counters
        out = {"scoring_wall_ms": self.per_fleet_ms(self.score_ns),
               "scoring_in_span_ms": self.per_fleet_ms(self.score_device_ns),
               "outside_run_ms": self.per_fleet_ms(self.window_ns
                                                   - self.run_ns)}
        if "events" in c:
            out["events"] = c["events"] / self.fleets
        if {"admit_ns", "step_ns", "netsim_ns"} <= set(c):
            out["admit_ms"] = self.per_fleet_ms(c["admit_ns"])
            out["sampler_ms"] = self.per_fleet_ms(c["step_ns"]
                                                  - c["netsim_ns"])
            out["netsim_ms"] = self.per_fleet_ms(c["netsim_ns"])
            out["engine_self_ms"] = self.per_fleet_ms(
                self.run_ns - self.score_ns - c["admit_ns"] - c["step_ns"])
        return out


def load(xplane_path: str) -> ProgramEvents:
    st = os.stat(xplane_path)
    return _load(xplane_path, st.st_mtime_ns, st.st_size)


@functools.lru_cache(maxsize=4)
def _load(xplane_path: str, _mtime_ns: int, _size: int) -> ProgramEvents:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    spans, windows, programs = [], [], []
    for plane in pd.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            programs.append([(e.start_ns, e.duration_ns)
                             for line in plane.lines
                             if line.name == "XLA Modules"
                             for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIX):
                        spans.append((e.name[len(PROGRAM_PREFIX):],
                                      e.start_ns, e.duration_ns,
                                      dict(e.stats)))
                    elif e.name == SPAN_PREFIX + "window":
                        windows.append((e.start_ns,
                                        e.start_ns + e.duration_ns))
    window = windows[0] if len(windows) == 1 else None
    return ProgramEvents(spans, window, programs)


def _clip(intervals, w0: float, w1: float) -> list[tuple[float, float]]:
    """``(start, end)`` of ``(start, duration)`` pairs, clipped to [w0, w1]."""
    out = [(max(s, w0), min(s + d, w1)) for s, d in intervals]
    return [(a, b) for a, b in out if b > a]


def _length(merged: list[tuple[float, float]]) -> float:
    return sum(b - a for a, b in merged)


def _overlap(a: list[tuple[float, float]],
             b: list[tuple[float, float]]) -> float:
    """Length of the intersection of two merged interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(hi - lo, 0.0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _merge(pairs: list[tuple[float, float]]) -> list[tuple[float, float]]:
    return trace.union([(a, b - a) for a, b in pairs])


def reduce(ev: ProgramEvents) -> Fleets | None:
    """The window divided among the fleets run inside it, or ``None`` where
    the trace holds no window or no ``repro.fleet.run`` span inside it."""
    if ev.window is None:
        return None
    w0, w1 = ev.window
    inside = [(name, s, d, stats) for name, s, d, stats in ev.spans
              if s >= w0 and s + d <= w1]
    runs = [x for x in inside if x[0] == "fleet.run"]
    if not runs:
        return None
    scores = _merge(_clip([(s, d) for name, s, d, _ in inside
                           if name == "fleet.score"], w0, w1))
    device = [_overlap(_merge(_clip(p, w0, w1)), scores)
              for p in ev.programs]
    counters: dict[str, float] = {}
    for _, _, _, stats in runs:
        for k in COUNTERS:
            if k in stats:
                counters[k] = counters.get(k, 0.0) + float(stats[k])
    return Fleets(
        fleets=len(runs),
        window_ns=w1 - w0,
        run_ns=_length(_merge(_clip([(s, d) for _, s, d, _ in runs],
                                    w0, w1))),
        score_ns=_length(scores),
        score_device_ns=sum(device) / len(device) if device else 0.0,
        counters=counters)


def find_xplane(log_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def metric(ctx: dict, name: str) -> float | None:
    """Metric ``name`` of :meth:`Fleets.metrics` for a traced run, read
    from the trace the run wrote under ``harness.TRACE_DIR``."""
    if ctx.get("summary") is None:
        return None
    path = find_xplane(str(harness.TRACE_DIR))
    if path is None:
        return None
    fleets = reduce(load(path))
    return None if fleets is None else fleets.metrics().get(name)
