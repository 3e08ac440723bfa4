"""Spans and busy-time counters of the program, written to the JAX profiler.

The profiler is the only channel.  A span is a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``, so it lands on the
trace's host plane on the same clock as the device's ``XLA Ops``; counters
are attached to a span when it closes (``set_metadata``) and come back as
the event's ``stats`` from ``jax.profiler.ProfileData``.  Nothing is kept or
written unless a profiler session records: capture with
``jax.profiler.trace(log_dir)`` around the calls.

:func:`fleet` opens one fleet's ``repro.fleet.run`` span under a
process-wide sequence number, and every :func:`span` opened inside it
carries that number as its ``fleet`` argument.

A span costs about a microsecond even with tracing off, too much once per
event, so the fleet's inner boundaries are :class:`Busy` counters: named
nanosecond totals that a caller wraps around a callable only while
:func:`active`.  A run with tracing off reads no clock.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import time

import jax

PREFIX = "repro."

_fleets = itertools.count()
_fleet = contextvars.ContextVar("repro_obs_fleet", default=None)


def active() -> bool:
    """True only while a profiler session records."""
    return jax.profiler.TraceAnnotation.is_enabled()


def span(name: str, **args):
    """The trace span ``repro.<name>``; inside :func:`fleet` it carries the
    fleet's number.  Use as a context manager; ``set_metadata`` on the
    annotation it yields attaches counters at close."""
    number = _fleet.get()
    if number is not None:
        args.setdefault("fleet", number)
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)


@contextlib.contextmanager
def fleet(**args):
    """The ``repro.fleet.run`` span of one fleet, under the next number."""
    token = _fleet.set(next(_fleets))
    try:
        with span("fleet.run", **args) as annotation:
            yield annotation
    finally:
        _fleet.reset(token)


def now_ns() -> int:
    # repro-lint: disable=DET001 -- busy-time counters are durations written
    # to the profiler trace; no simulated clock or decision ever reads them.
    return time.perf_counter_ns()


class Busy:
    """Named busy-time totals: ``totals[name]`` is ``[nanoseconds, calls]``."""

    def __init__(self) -> None:
        self.totals: dict[str, list[int]] = {}

    def timed(self, name: str, fn):
        """``fn``, wrapped so that each call adds its duration to ``name``."""
        total = self.totals.setdefault(name, [0, 0])

        def wrapper(*args, **kwargs):
            t0 = now_ns()
            try:
                return fn(*args, **kwargs)
            finally:
                total[0] += now_ns() - t0
                total[1] += 1

        return wrapper
