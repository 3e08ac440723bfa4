"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

Two steps, kept apart so the second can be checked on recorded events:

* :func:`load_events` reads the trace with ``jax.profiler.ProfileData`` and
  keeps, for every TPU device plane, the intervals of its ``XLA Ops`` line
  (what ran on the chip) and of its ``XLA Modules`` line (which jitted
  program ran), plus the harness's own host spans (``bench.*``
  ``TraceAnnotation``s) from the host plane.  All times are nanoseconds on
  the trace's common clock.
* :func:`summarize` turns those events into the busy union, the idle share
  of the traced window, the device time of each program, the operations
  that took most time and the longest idle gaps, each named by the
  innermost harness span that covered it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

from benchmarks.chip.clock import SPAN_PREFIX

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_MODULE_ID = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Events:
    """Events read from one trace; times in nanoseconds."""

    ops: list[list[tuple[float, float]]]  # per device: XLA Ops (start, dur)
    modules: list[list[tuple[str, float, float]]]  # per device: programs
    spans: list[tuple[str, float, float]]  # harness host spans (name, ...)


@dataclasses.dataclass
class Summary:
    n_devices: int
    busy_s: float  # union of op intervals, averaged over devices
    window_s: float
    idle_share: float
    program_s: dict[str, float]  # device seconds per program in the window
    top_ops: list[tuple[str, float]]  # programs that took most time
    idle_gaps: list[tuple[str, float]]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def program_name(module_event_name: str) -> str:
    """``jit_refine_and_stats(98359...)`` -> ``refine_and_stats``."""
    name = _MODULE_ID.sub("", module_event_name)
    return name[4:] if name.startswith("jit_") else name


def load_events(xplane_path: str) -> Events:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    ops, modules, spans = [], [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            o, m = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    o = [(e.start_ns, e.duration_ns) for e in line.events]
                elif line.name == "XLA Modules":
                    m = [(program_name(e.name), e.start_ns, e.duration_ns)
                         for e in line.events]
            ops.append(o)
            modules.append(m)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):], e.start_ns,
                                      e.duration_ns))
    return Events(ops, modules, spans)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` intervals of ``(start, duration)`` pairs."""
    out: list[list[float]] = []
    for s, d in sorted(intervals):
        e = s + max(d, 0.0)
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(a, b) for a, b in out]


def _innermost(spans, t: float) -> str:
    best, best_len = "untraced host", float("inf")
    for name, s, d in spans:
        if s <= t <= s + d and d < best_len:
            best, best_len = name, d
    return best


def summarize(ev: Events, window_ns: tuple[float, float] | None = None,
              top: int = 10) -> Summary:
    """Busy/idle, per-program time and the breakdown of one traced window.

    ``window_ns`` is the traced window on the trace's clock; by default it
    is the harness's ``window`` span, else the extent of all harness spans,
    else that of the device events.
    """
    n_dev = len(ev.ops)
    if n_dev == 0:
        raise ValueError("trace holds no TPU device plane")
    if window_ns is None:
        win = [(s, s + d) for name, s, d in ev.spans if name == "window"]
        pts = win or [(s, s + d) for _, s, d in ev.spans] or [
            (s, s + d) for o in ev.ops for s, d in o]
        window_ns = (min(a for a, _ in pts), max(b for _, b in pts))
    w0, w1 = window_ns
    busy = 0.0
    gaps = []
    for o in ev.ops:
        u = [(max(a, w0), min(b, w1))
             for a, b in union(o)]
        u = [(a, b) for a, b in u if b > a]
        busy += sum(b - a for a, b in u)
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, (a + b) / 2))
    busy_s = busy / n_dev * 1e-9
    window_s = (w1 - w0) * 1e-9
    program_s: dict[str, float] = {}
    for m in ev.modules:
        for name, s, d in m:
            inside = min(s + d, w1) - max(s, w0)
            if inside > 0:
                program_s[name] = program_s.get(name, 0.0) + inside * 1e-9
    top_ops = sorted(program_s.items(), key=lambda kv: -kv[1])[:top]
    gaps.sort(reverse=True)
    idle_gaps = [(_innermost(ev.spans, mid), g * 1e-9)
                 for g, mid in gaps[:top]]
    return Summary(n_dev, busy_s, window_s,
                   1.0 - busy_s / window_s if window_s > 0 else float("nan"),
                   program_s, top_ops, idle_gaps)
