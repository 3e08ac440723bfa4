"""Sharded fleet engine: the vectorized event loop, or windowed rounds.

:class:`ShardedFleetEngine` runs one of two regimes over the vectorized
engine's per-run bookkeeping (``_FleetRun``: admission, recovery, finish
fold-in and the report's counts), so only the loop differs:

* **strict** — the parity regime, and the default at parity scale.  It is
  :meth:`VectorizedFleetEngine.run` itself, one global ``(time, slot)``
  heap and all, so every RNG stream, the canonical trace and the
  ``FleetReport`` are *bit-identical* to ``VectorizedFleetEngine``
  (``tests/test_engine_shard.py`` locks this in across the scenario
  matrix).

* **windowed** — the scale regime, selected automatically above
  ``AUTO_CONTENTION_CUTOVER`` (or forced via
  ``EngineConfig.shard_window_s``).  Zero-lookahead coupling through the
  shared link makes bit-identical parallel execution impossible — every
  chunk's rate depends on every concurrent registration — so above parity
  scale the engine relaxes to bulk-synchronous windows of width
  ``shard_window_s``.  Slots are partitioned cyclically across
  ``n_shards`` host-side heaps (``ShardedEventFrontier``,
  ``repro.dist.sharding.slot_shard``); each shard drains its own heap
  through the window as an uninterrupted burst per session (intra-window
  events never touch the heap), contention, external load and the
  limiter's active count are frozen at the window start
  (``WindowedLinkState`` / ``WindowTenantEnvironment``), buffered flow
  registrations fold into the ``IndexedSharedLink`` running sum at the
  merge point, and the run's finish bookkeeping runs at the window barrier
  in global ``(clock, slot)`` order.  Still fully deterministic — same
  config, same report — but one coarsening level beyond the per-chunk
  quasi-static discipline the strict link already documents.

``n_shards`` defaults to the host's device count, and the windowed regime
needs ``n_shards > 1``; no shard runs on a device.
"""

from __future__ import annotations

import functools

from repro.core.engine.shard import (
    ShardedEventFrontier,
    WindowedLinkState,
    WindowEpoch,
    WindowTenantEnvironment,
)
from repro.core.engine.vectorized import (
    AUTO_CONTENTION_CUTOVER,
    PHASE_FINISH,
    VectorizedFleetEngine,
    _ActiveCounter,
    _FleetRun,
)
from repro.core.fleet import FleetReport, FleetRequest, assemble_fleet_report
from repro.netsim.environment import IndexedSharedLink
from repro.netsim.testbeds import TESTBEDS

#: Window width of the auto-selected windowed regime.  Wide enough that a
#: typical bulk chunk completes inside one window (so sessions burst through
#: several interactions per merge), narrow against the diurnal period (3 h)
#: so frozen load/contention stay representative.
DEFAULT_SHARD_WINDOW_S = 120.0


class _FrozenActiveCount:
    """``n_active_fn`` for the windowed regime.

    The strict engines hand the re-probe limiter the exact active count at
    each gate event; the windowed regime freezes it at the window start —
    the same one-level coarsening as the contention aggregate, and equally
    deterministic.
    """

    def __init__(self, counter: _ActiveCounter):
        self._counter = counter
        self._value = 0

    def freeze(self, t0_s: float) -> None:
        self._value = self._counter(t0_s)

    def __call__(self, now_s: float) -> int:
        return self._value


class ShardedFleetEngine(VectorizedFleetEngine):
    """Run N concurrent sessions strictly, or in windows over ``n_shards``
    host-side event heaps.

    ``config`` is an ``EngineConfig`` with ``engine="sharded"``;
    ``n_shards=None`` resolves to the host's device count and
    ``shard_window_s=None`` picks the regime automatically (strict at
    parity scale, windowed above the contention cutover).
    """

    def __init__(self, db, config):
        super().__init__(db, config)
        self.n_shards = self._resolve_n_shards(config)
        self.windows_run = 0

    # ------------------------------------------------------------------ #
    @staticmethod
    def _resolve_n_shards(config) -> int:
        n = getattr(config, "n_shards", None)
        if n is not None:
            return int(n)
        # Deferred import: backend init must happen after the entry point
        # has set its XLA flags (the same discipline repro.dist documents).
        import jax

        return int(jax.local_device_count())

    def _window_s(self, n: int) -> float | None:
        """Window width for this run, or ``None`` for the strict regime."""
        if self.n_shards <= 1:
            return None  # nothing to reconcile across shards
        w = getattr(self.config, "shard_window_s", None)
        if w is None:
            return DEFAULT_SHARD_WINDOW_S if n > AUTO_CONTENTION_CUTOVER else None
        if w <= 0.0:
            return None  # 0 forces strict at any scale
        return float(w)

    # ------------------------------------------------------------------ #
    def run(self, requests: list[FleetRequest]) -> FleetReport:
        window = self._window_s(len(requests))
        if window is None or not requests:
            return super().run(requests)
        return self._run_windowed(requests, window)

    # ------------------------------------------------------------------ #
    def _run_windowed(
        self, requests: list[FleetRequest], window: float
    ) -> FleetReport:
        """The bulk-synchronous scale regime (see the module docstring):
        burst per shard until the window end, then a barrier that exchanges
        link state and hands finishes to the run in global order."""
        link = TESTBEDS[self.config.testbed]
        shared = WindowedLinkState(IndexedSharedLink(link))
        epoch = WindowEpoch()
        frontier = ShardedEventFrontier(
            self.n_shards, capacity=max(2 * len(requests), 16)
        )
        make_env = functools.partial(
            self._make_tenant_env,
            shared=shared,
            env_cls=WindowTenantEnvironment,
            epoch=epoch,
        )
        run = _FleetRun(
            self.db,
            self.config,
            requests,
            frontier,
            make_env,
            active_view=_FrozenActiveCount,
        )
        self.state = state = run.state
        gens, envs, reports = run.gens, run.envs, run.reports
        run.start()

        # ---------------- the window loop ---------------- #
        while len(frontier):
            t0 = frontier.peek()[0]
            w_end = t0 + window
            self.windows_run += 1
            epoch.advance()  # invalidate every per-tenant load cache
            shared.begin_window(t0)  # fold buffered flows, freeze aggregate
            run.n_active.freeze(t0)
            finished: list[tuple[float, int]] = []
            for shard in frontier.shards:
                while len(shard) and shard.peek()[0] < w_end:
                    _, i = shard.pop()
                    if state.phase[i] == PHASE_FINISH:
                        finished.append((float(state.next_event_s[i]), i))
                        continue
                    self._burst(i, w_end, gens, envs, reports, state, shard, finished)
            # Window barrier: finish bookkeeping in global (clock, slot)
            # order — the same per-finish sequence as the strict loop.
            for now, i in sorted(finished):
                self.events_processed += 1
                run.finish(i, now)

        return assemble_fleet_report(
            self.db, self.config.testbed, run.requests, **run.report_kwargs()
        )

    # ------------------------------------------------------------------ #
    def _burst(self, i, w_end, gens, envs, reports, state, shard, finished):
        """Resume slot ``i`` through every interaction before ``w_end``.

        Intra-window events are absorbed without heap traffic: only the
        first yield at or beyond the window end goes back on the shard heap
        (or, if the session returns first, its finish record into the
        window's merge buffer).  Per-slot state arrays are written at the
        burst boundary only — mid-burst phases are never observable at a
        barrier, so ``live_histogram`` stays consistent where it is read.
        """
        gen = gens[i]
        while True:
            try:
                t, phase, prm = next(gen)
            except StopIteration as stop:
                reports[i] = stop.value
                state.phase[i] = PHASE_FINISH
                t_fin = envs[i].clock_s
                state.next_event_s[i] = t_fin
                if t_fin < w_end:
                    finished.append((t_fin, i))
                else:
                    shard.push(t_fin, i)
                return
            self.events_processed += 1
            if t >= w_end:
                state.phase[i] = phase
                state.params[i] = prm.as_tuple()
                state.next_event_s[i] = t
                shard.push(t, i)
                return
