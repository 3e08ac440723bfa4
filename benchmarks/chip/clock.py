"""Clocks the harness reads: compiles, host spans and the compile cache.

``CompileClock`` counts XLA backend compiles and persistent-cache hits from
``jax.monitoring`` events; ``Spans`` records the harness's own host spans
around each call into the program and mirrors them into the profiler's
trace as ``TraceAnnotation``s, so idle gaps of the device can be named by
what the host was doing.  ``enable_compile_cache`` keeps JAX's persistent
compilation cache at one fixed directory inside the checkout (or where
``JAX_COMPILATION_CACHE_DIR`` says).
"""
from __future__ import annotations

import contextlib
import os
import pathlib
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
CACHE_DIR = ROOT / ".jax_cache"
SPAN_PREFIX = "bench."


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory.

    Every compile is cached, however short: the discovery sweeps and the
    scoring programs compile in well under JAX's one-second default.
    """
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


class CompileClock:
    """Sums XLA backend-compile seconds and persistent-cache hits/misses."""

    def __init__(self) -> None:
        import jax.monitoring

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Spans:
    """Host spans of the harness: ``name -> [(start_s, end_s), ...]``."""

    def __init__(self) -> None:
        self.spans: dict[str, list[tuple[float, float]]] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
                yield
        finally:
            self.spans.setdefault(name, []).append((t0, time.perf_counter()))
