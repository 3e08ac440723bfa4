"""Event-driven vectorized fleet engine (the ROADMAP's million-session item).

The threaded ``FleetScheduler`` is correct and deterministic but structurally
capped: one Python thread per session, each interaction serialized through a
condition-variable handshake.  This engine keeps the *logical* schedule —
interactions execute in ascending ``(simulated clock, tenant id)`` order, the
same conservative discrete-event discipline as ``_FleetClock`` — but replaces
the threads with a single event loop over suspended session generators:

* every session is an ``AdaptiveSampler.session`` generator that yields
  ``(clock_s, phase, params)`` immediately before each environment
  interaction (probe transfer, bulk chunk, re-probe-gate consultation);
* per-session scheduling state is stacked in flat numpy arrays
  (:class:`FleetStateArrays`: phase, last-yielded params, next-event time,
  admit/end clocks);
* the next interaction fleet-wide is popped from a
  :class:`~repro.core.engine.heap.VectorEventHeap` keyed ``(clock, slot)``
  with the clock's exact tie rule, and exactly one generator is resumed per
  event.

Because both engines execute the same per-session code (the generator) under
the same global interleaving (same keys, same tie-break), with the same RNG
streams, the same admission/recovery/refresh bookkeeping at the same
simulated instants, and a report assembled by the shared
``assemble_fleet_report``, the ``FleetReport`` is *bit-identical* to the
threaded oracle — ``tests/test_engine_vec.py`` locks this in across the
scenario matrix.  What changes is capacity: no thread stacks, no handshakes,
O(log N) scheduling, and (above the parity regime) O(log N) contention
bookkeeping via ``IndexedSharedLink``, which is what takes fleets from
hundreds of sessions to 1e5+ (``benchmarks/fleet_scale.py``).

Everything of a run but its loop is one per-run object, ``_FleetRun``:
the attempt-indexed state, the arrival-ordered queue, where an admission's
knowledge comes from (the knowledge service, the fleet's routing pass, or
``db.query``), and where a finished session's goes, with recovery
re-admission and the report's counts.  Two loops drive it: this engine's,
one event at a time, and the windowed rounds of ``ShardedFleetEngine``,
whose strict regime is this engine's loop itself.

The batched-kernel path is unchanged: admission demand prediction still goes
through ``SurfaceStack.best_candidates`` (vmapped gather or the Pallas
kernel) via the shared module-level ``auto_concurrency``.  Where the
knowledge is frozen for the run, that pass's routing is also each first
attempt's cluster at admission (:func:`plan_admission`), so a request is
routed once per fleet.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import heapq

import numpy as np

from repro import obs
from repro.core.fleet import (
    FleetReport,
    FleetRequest,
    ReprobeLimiter,
    assemble_fleet_report,
    auto_concurrency,
    with_link_load,
)
from repro.core.offline import OfflineDB
from repro.core.online import (
    AdaptiveSampler,
    TransferReport,
    request_features,
)
from repro.core.refresh import KnowledgeRefresher
from repro.core.engine.heap import VectorEventHeap
from repro.netsim.environment import (
    IndexedSharedLink,
    SharedLink,
    TenantEnvironment,
)
from repro.netsim.testbeds import TESTBEDS, make_traffic

# Slot phases: 1-3 mirror the ``AdaptiveSampler.session`` yield tags
# (PHASE_PROBE / PHASE_BULK / PHASE_GATE); the engine adds the two
# scheduling-only states.
PHASE_IDLE = 0  # not admitted yet, or fully retired
PHASE_FINISH = 4  # session returned; finish bookkeeping event is queued

#: Above this fleet size ``contention="auto"`` switches from the exact
#: ``SharedLink`` (bit-identical to the threaded oracle, O(N) per snapshot)
#: to ``IndexedSharedLink`` (numerically equal, O(log N)).  Parity tests run
#: far below this line, so "auto" is both oracle-exact where it is checked
#: and scalable where it matters.
AUTO_CONTENTION_CUTOVER = 1024


def plan_admission(db: OfflineDB, cfg, requests, link):
    """``(cap, routes)`` of a run: the admission cap, and where the cap
    was computed and the knowledge is frozen for the run (no refresher, no
    knowledge service), the cluster index of each request from that same
    pass, else ``None``.  Admission reads ``db.clusters[routes[i]]`` for a
    first attempt ``i`` in place of ``db.query``: the same float64
    nearest-centroid routing, done once.  Recovery re-admissions, which
    have slots past the fleet's requests, still query."""
    if cfg.max_concurrent:
        return cfg.max_concurrent, None
    plan = auto_concurrency(
        db,
        requests,
        link,
        testbed=cfg.testbed,
        overcommit=cfg.overcommit,
        use_pallas=cfg.use_pallas,
    )
    frozen = cfg.refresh is None and getattr(cfg, "knowledge", None) is None
    return plan.cap, plan.clusters.tolist() if frozen else None


@dataclasses.dataclass
class FleetStateArrays:
    """Per-slot session state stacked as flat numpy arrays.

    One row per admitted attempt slot: the yield tag the session is paused
    on (``phase``), the parameters it is about to use (``params``), when its
    next interaction fires (``next_event_s``), and its admit/end clocks.
    ``phase`` drives event dispatch in the engine loop; the rest make fleet
    state O(1)-inspectable mid-run (``live_histogram``) instead of buried in
    N generator frames.
    """

    phase: np.ndarray  # int8 — PHASE_IDLE/PROBE/BULK/GATE/FINISH
    params: np.ndarray  # int32 (n, 3) — last yielded (cc, p, pp)
    next_event_s: np.ndarray  # float64 — heap key of the pending event
    admit_s: np.ndarray  # float64
    end_s: np.ndarray  # float64

    @classmethod
    def allocate(cls, n: int) -> "FleetStateArrays":
        n = max(n, 1)
        return cls(
            phase=np.zeros(n, np.int8),
            params=np.zeros((n, 3), np.int32),
            next_event_s=np.full(n, np.inf, np.float64),
            admit_s=np.zeros(n, np.float64),
            end_s=np.zeros(n, np.float64),
        )

    def grow_to(self, n: int) -> None:
        cap = self.phase.shape[0]
        if n <= cap:
            return
        while cap < n:
            cap *= 2
        for name in ("phase", "params", "next_event_s", "admit_s", "end_s"):
            old = getattr(self, name)
            shape = (cap,) + old.shape[1:]
            fill = np.inf if name == "next_event_s" else 0
            new = np.full(shape, fill, old.dtype)
            new[: old.shape[0]] = old
            setattr(self, name, new)

    def live_histogram(self, n_slots: int) -> dict[int, int]:
        """``{phase: count}`` over the first ``n_slots`` slots."""
        tags, counts = np.unique(self.phase[:n_slots], return_counts=True)
        return {int(t): int(c) for t, c in zip(tags, counts)}


class _ActiveCounter:
    """Exact incremental replacement for ``_FleetClock.n_active_at``.

    The threaded clock answers "how many tenants are live at ``t``" by
    scanning every tenant; at 1e5+ sessions the limiter would turn that into
    the quadratic hot path.  This counter maintains the same quantity
    incrementally: +1 when a tenant's admit time is reached, -1 when its
    finish event is processed.  Queries arrive in event order — the engine
    serializes interactions by ascending ``(clock, slot)`` exactly like the
    threaded turn discipline — so time is monotone and a tenant's activation
    can be drained lazily from a min-heap of future admit times.  A finished
    tenant stops counting from its finish *event* onward, which is precisely
    when ``_FleetClock.finish`` flips ``done`` in the threaded engine (both
    engines order that event by the same ``(end_clock, slot)`` key).
    """

    def __init__(self):
        self._active = 0
        self._future: list[float] = []  # min-heap of pending admit times

    def admit(self, admit_s: float) -> None:
        heapq.heappush(self._future, admit_s)

    def finish(self, now_s: float) -> None:
        self(now_s)  # the finishing tenant's own +1 lands before the -1
        self._active -= 1

    def __call__(self, now_s: float) -> int:
        while self._future and self._future[0] <= now_s:
            heapq.heappop(self._future)
            self._active += 1
        return self._active


class _FleetRun:
    """One fleet run's bookkeeping: everything of a run but its event loop.

    Both loops drive one: the vectorized engine's, which pops one event at
    a time, and the sharded engine's windowed rounds.  It holds the
    attempt-indexed state, laid out exactly like the threaded scheduler's
    (slots ``0..n-1`` are first attempts, recovery re-admissions append
    further slots), the arrival-ordered queue, the active count and the
    re-probe limiter, and where an admission's knowledge comes from and a
    finished session's goes.  A loop calls :meth:`start`, then
    :meth:`finish` at each finish event in ``(clock, slot)`` order, and
    ends with :meth:`report_kwargs`.

    ``heap`` takes each session's next event; ``make_env(request, slot)``
    builds a tenant's environment.  ``busy`` (``obs.Busy`` or ``None``)
    times admission and each environment's transfer and load.
    ``active_view`` wraps the active counter for the limiter (the windowed
    regime freezes it per window); by default the limiter reads it exactly.
    """

    def __init__(
        self, db, cfg, requests, heap, make_env, *, busy=None, active_view=None
    ):
        self.db, self.cfg = db, cfg
        self.requests = requests = with_link_load(requests, cfg.testbed)
        n = len(requests)
        self.link = link = TESTBEDS[cfg.testbed]
        self.heap, self.make_env, self.busy = heap, make_env, busy
        self.step = next if busy is None else busy.timed("step", next)
        self.counter = counter = _ActiveCounter()
        self.n_active = counter if active_view is None else active_view(counter)
        # The limiter is consulted directly (no turn wrapper): gate events
        # already arrive in simulated-time order.
        self.limiter = ReprobeLimiter(cfg.reprobe_interval_s, n_active_fn=self.n_active)
        knowledge = getattr(cfg, "knowledge", None)
        if knowledge is not None and knowledge.db_for(None) is not db:
            raise ValueError(
                "knowledge service must serve the same OfflineDB the "
                "engine runs against"
            )
        self.knowledge = knowledge
        self.refresher = (
            KnowledgeRefresher(db, link, cfg.refresh)
            if cfg.refresh is not None and knowledge is None
            else None
        )
        # Service counters are cumulative across runs; report the delta.
        self._k_stats0 = knowledge.stats() if knowledge is not None else None
        self.cap, self.routes = plan_admission(db, cfg, requests, link)

        self.reqs: list[FleetRequest] = list(requests)
        self.origin = list(range(n))
        self.attempt_no = [0] * n
        self.reports: list[TransferReport | None] = [None] * n
        self.end_clock = [0.0] * n
        self.admit_time = [0.0] * n
        self.gens: list = [None] * n
        self.envs: list[TenantEnvironment | None] = [None] * n
        self.state = FleetStateArrays.allocate(n)
        self.pending = collections.deque(
            sorted(range(n), key=lambda i: (requests[i].start_clock_s, i))
        )
        self.n_kills = 0
        self.n_recoveries = 0
        self.n_const_load = 0  # admissions on constant traffic, traced only
        self.n_prerouted = 0  # admissions on the fleet's routing pass
        self.admit = self._build if busy is None else busy.timed("admit", self._build)

    def start(self) -> None:
        """The initial admission wave, before any event runs — mirrors the
        threaded engine admitting (and clock-registering) the whole wave
        before starting worker threads."""
        for _ in range(min(self.cap, len(self.requests))):
            self.admit_next(float("-inf"))

    def _build(self, now_s: float) -> int:
        """Admit the next queued request into its slot, up to the
        session's first step."""
        cfg, reqs, busy = self.cfg, self.reqs, self.busy
        i = self.pending.popleft()
        req = reqs[i]
        admit_s = self.admit_time[i] = max(req.start_clock_s, now_s)
        self.state.admit_s[i] = admit_s
        # Knowledge snapshot resolved at admission, in event order — the
        # same refresh-consistency point as the threaded engine.
        if self.knowledge is not None:
            feats = request_features(self.link, req.dataset)
            cluster = self.knowledge.query_cluster(None, feats)
            budget = self.knowledge.probe_budget(None, admit_s, cfg.max_samples)
        elif self.routes is not None and i < len(self.routes):
            cluster = self.db.clusters[self.routes[i]]
            self.n_prerouted += 1
            budget = cfg.max_samples
        else:
            cluster = self.db.query(request_features(self.link, req.dataset))
            budget = cfg.max_samples
        env = self.make_env(req, i)
        if busy is not None:
            env.transfer = busy.timed("netsim", env.transfer)
            if getattr(env.traffic, "is_constant", False):
                self.n_const_load += 1
            else:
                # the shared link load's reading, inside the transfer's; a
                # constant load is left untimed, so the timer adds nothing
                # to its fleets' netsim_ns
                env.current_load = busy.timed("load", env.current_load)
        env.clock_s = admit_s
        self.envs[i] = env
        self.counter.admit(admit_s)
        sampler = AdaptiveSampler(
            self.db,
            z=cfg.z,
            max_samples=budget,
            bulk_chunks=cfg.bulk_chunks,
            reprobe_gate=self.limiter,
            recovery=cfg.recovery,
        )
        self.gens[i] = sampler.session(env, req.dataset, cluster)
        return i

    def admit_next(self, now_s: float) -> None:
        """Admit the next queued request, if any, through its first step."""
        if self.pending:
            i = self.admit(now_s)
            _advance(
                i, self.gens, self.envs, self.reports, self.state, self.heap, self.step
            )

    def finish(self, i: int, now_s: float) -> None:
        """Slot ``i``'s finish event at ``now_s``, in the same order as the
        threaded worker's final serialized turn: fold knowledge in, re-admit
        the killed session's residual, admit the next queued request, then
        stop counting as active."""
        self.end_clock[i] = now_s
        state = self.state
        state.end_s[i] = now_s
        rep = self.reports[i]
        if rep is not None:
            if self.knowledge is not None:
                # The service handles interrupted/collapsed sessions itself
                # (fault signal, no fold-in).
                self.knowledge.observe(
                    rep, self.reqs[i].dataset, link=self.link, now_s=now_s
                )
            elif self.refresher is not None and not rep.interrupted:
                self.refresher.observe(rep, self.reqs[i].dataset, now_s=now_s)
            if rep.interrupted:
                self._enqueue_recovery(i, rep, now_s)
        self.admit_next(now_s)
        self.counter.finish(now_s)
        state.phase[i] = PHASE_IDLE
        self.gens[i] = None
        self.envs[i] = None  # free generator frame + env at scale

    def _enqueue_recovery(self, i: int, rep: TransferReport, now_s: float):
        self.n_kills += 1
        recovery, req = self.cfg.recovery, self.reqs[i]
        if (
            recovery is None
            or self.attempt_no[i] >= recovery.max_restarts
            or rep.moved_mb >= req.dataset.total_mb - 1e-9
        ):
            return
        self.n_recoveries += 1
        nxt = dataclasses.replace(
            req,
            dataset=req.dataset.residual(rep.moved_mb),
            start_clock_s=now_s + recovery.restart_delay_s,
            env_seed=req.env_seed + 101,
        )
        j = len(self.reqs)
        self.reqs.append(nxt)
        self.origin.append(self.origin[i])
        self.attempt_no.append(self.attempt_no[i] + 1)
        self.reports.append(None)
        self.end_clock.append(0.0)
        self.admit_time.append(0.0)
        self.gens.append(None)
        self.envs.append(None)
        self.state.grow_to(len(self.reqs))
        self.pending.append(j)

    def report_kwargs(self) -> dict:
        """``assemble_fleet_report``'s keyword arguments for this run."""
        if self.knowledge is not None:
            now, then = self.knowledge.stats(), self._k_stats0
            refreshes = now.refits - then.refits
            entries = now.entries_folded - then.entries_folded
        elif self.refresher is not None:
            refreshes = self.refresher.refreshes
            entries = self.refresher.entries_folded
        else:
            refreshes = entries = 0
        return dict(
            reqs=self.reqs,
            origin=self.origin,
            attempt_no=self.attempt_no,
            reports=self.reports,
            end_clock=self.end_clock,
            admit_time=self.admit_time,
            score_vs_single=self.cfg.score_vs_single,
            reprobe_grants=self.limiter.grants,
            reprobe_denials=self.limiter.denials,
            admitted_concurrency=min(self.cap, len(self.requests)),
            refreshes=refreshes,
            refreshed_entries=entries,
            kills=self.n_kills,
            recoveries=self.n_recoveries,
        )


class VectorizedFleetEngine:
    """Run N concurrent sessions as one event loop, oracle-parity guaranteed.

    ``config`` is an ``EngineConfig`` (see ``repro.core.engine.api``); the
    engine reads its fleet knobs (testbed, admission, limiter, refresh,
    faults, recovery, sampler parameters) and the ``contention`` selector.
    """

    def __init__(self, db: OfflineDB, config):
        self.db = db
        self.config = config
        self.events_processed = 0
        self.state: FleetStateArrays | None = None
        # the last run's counters, kept only while a profiler session
        # records (``repro.obs``): events, admissions and busy nanoseconds
        self.counters: dict[str, int] | None = None

    # ------------------------------------------------------------------ #
    def _make_shared(self, link, n: int):
        mode = getattr(self.config, "contention", "auto")
        if mode == "exact" or (mode == "auto" and n <= AUTO_CONTENTION_CUTOVER):
            return SharedLink(link)
        return IndexedSharedLink(link)

    def _make_tenant_env(
        self,
        req: FleetRequest,
        tenant_id: int,
        shared,
        env_cls=TenantEnvironment,
        **env_kw,
    ) -> TenantEnvironment:
        # the link, traffic, seed and noise ``make_testbed`` gives the
        # threaded oracle's tenants, with no environment built to read them
        traffic = req.traffic
        if traffic is None:
            traffic = make_traffic(
                self.config.testbed,
                seed=req.env_seed,
                constant_load=req.constant_load,
            )
        return env_cls(
            TESTBEDS[self.config.testbed],
            traffic,
            shared,
            tenant_id,
            seed=req.env_seed,
            turn_gate=None,  # the event loop itself is the serializer
            faults=self.config.faults,
            **env_kw,
        )

    # ------------------------------------------------------------------ #
    def run(self, requests: list[FleetRequest]) -> FleetReport:
        cfg = self.config
        n = len(requests)
        if n == 0:
            return FleetReport([], 0.0, 0.0, 0.0, 0.0, 0.0, 0, 0, 0)
        # Busy-time counters only while a profiler session records; with
        # tracing off the loop calls the plain callables and reads no clock.
        busy = obs.Busy() if obs.active() else None
        shared = self._make_shared(TESTBEDS[cfg.testbed], n)
        heap = VectorEventHeap(capacity=max(2 * n, 16))
        make_env = functools.partial(self._make_tenant_env, shared=shared)
        run = _FleetRun(self.db, cfg, requests, heap, make_env, busy=busy)
        self.state = state = run.state
        run.start()

        # ---------------- the event loop ---------------- #
        pop, advance, finish, step = heap.pop, _advance, run.finish, run.step
        gens, envs, reports = run.gens, run.envs, run.reports
        events = 0
        while len(heap):
            _, i = pop()
            events += 1
            if state.phase[i] == PHASE_FINISH:
                finish(i, envs[i].clock_s)
                continue
            advance(i, gens, envs, reports, state, heap, step)
        self.events_processed += events

        self.counters = None
        if busy is not None:
            self.counters = {
                "events": events,
                "admissions": busy.totals["admit"][1],
                "const_load": run.n_const_load,
                "prerouted": run.n_prerouted,
                "reprobe_grants": run.limiter.grants,
                "load_ns": 0,  # no shared load: every tenant's is constant
                **{f"{k}_ns": ns for k, (ns, _) in busy.totals.items()},
            }
        return assemble_fleet_report(
            self.db, cfg.testbed, run.requests, **run.report_kwargs()
        )


def _advance(i, gens, envs, reports, state, heap, step=next) -> None:
    """Resume slot ``i``'s generator through exactly one interaction.

    The generator performs the environment interaction it announced with
    its previous yield, then either announces the next one (re-queue at its
    new clock) or returns its ``TransferReport`` (queue the finish event at
    the session's final clock — the same key as the threaded worker's final
    turn).  ``step`` is ``next``, or ``next`` timed.
    """
    try:
        t, phase, prm = step(gens[i])
    except StopIteration as stop:
        reports[i] = stop.value
        state.phase[i] = PHASE_FINISH
        state.next_event_s[i] = envs[i].clock_s
        heap.push(envs[i].clock_s, i)
        return
    state.phase[i] = phase
    state.params[i] = prm.as_tuple()
    state.next_event_s[i] = t
    heap.push(t, i)
