"""Fleet-scale online tuning: N concurrent sessions over one shared link.

Contention-aware multi-transfer scheduling for the production regime the
single-transfer paper (Algorithm 1) does not cover: many simultaneous
requests probing and bulk-transferring over the same path, the regime the
two-phase follow-up work (arXiv:1812.11255) studies.

Design:

* Each tenant runs the unmodified scalar Algorithm-1 session
  (``AdaptiveSampler``) in its own thread against a
  ``netsim.TenantEnvironment``.  A conservative simulated-time serializer
  (``_FleetClock``) only ever lets the tenant with the minimum clock (ties by
  id) interact with the environment, so runs are deterministic and an N=1
  fleet reproduces the single-tenant ``TransferReport`` bit-for-bit.
* Contention enters through ``netsim.SharedLink``: concurrent active
  transfers divide capacity fair-share on top of the paper's external-load
  model.
* Re-probe storms — every tenant re-parameterizing at once when a capacity
  swing knocks the whole fleet out of its confidence bands — are rate-limited
  by a fleet-wide ``ReprobeLimiter``.
* Admission is contention-aware: the batched surface path (``core.batched``)
  scores every request x surface x candidate point in one vmapped call, and
  the scheduler caps concurrent admissions near the link's predicted
  capacity, queueing the rest behind finishing transfers.

Per-request ``TransferReport``s roll up into a ``FleetReport`` with aggregate
goodput, p50/p99 convergence sample counts, and mean accuracy against the
single-tenant optimum.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
from typing import NamedTuple

import numpy as np

from repro import obs
from repro.core.offline import OfflineDB
from repro.core.online import (
    AdaptiveSampler,
    RecoveryConfig,
    TransferReport,
    request_features,
)
from repro.core.refresh import KnowledgeRefresher, RefreshConfig
from repro.netsim.environment import Environment, SharedLink, TenantEnvironment
from repro.netsim.testbeds import TESTBEDS, make_link_load, make_testbed
from repro.netsim.workload import Dataset


@dataclasses.dataclass(frozen=True)
class FleetRequest:
    """One tenant's transfer request.

    ``traffic`` overrides the testbed's diurnal background-load model for
    this tenant's path; it must be stateless/deterministic (a pure function
    of simulated time, e.g. ``netsim.RegimeShiftTraffic``) so fleet runs
    stay reproducible and instances can be shared across tenants.  A
    request with neither ``traffic`` nor ``constant_load`` runs under the
    link's one diurnal load, which every engine builds once per fleet
    (:func:`with_link_load`).
    """

    dataset: Dataset
    env_seed: int = 0
    start_clock_s: float = 0.0
    constant_load: float | None = None  # pin external load (tests/benchmarks)
    traffic: object | None = None  # custom external-load model


@dataclasses.dataclass
class FleetConfig:
    testbed: str = "xsede"
    max_concurrent: int | None = None  # None = auto from batched predictions
    overcommit: float = 2.0  # admitted demand may exceed capacity by this
    reprobe_interval_s: float = 5.0  # fleet-wide min spacing of re-probes
    score_vs_single: bool = True  # compute accuracy vs single-tenant optimum
    refresh: RefreshConfig | None = None  # continuous knowledge refresh; None
    # = off, which reproduces refresh-free fleet runs bit-for-bit
    faults: object | None = None  # netsim.FaultSchedule shared by all tenant
    # envs; None keeps every environment on the fault-free fast path
    recovery: RecoveryConfig | None = None  # collapse re-probing + killed-
    # session re-admission; None reproduces pre-recovery behaviour exactly


@dataclasses.dataclass
class SessionOutcome:
    """One admitted session attempt — recovery re-admissions of a killed
    request appear as further attempts with the same ``request_index``."""

    request_index: int  # original request this attempt serves
    attempt: int  # 0 = first admission, 1+ = recovery re-admissions
    tenant_id: int  # fleet-clock tenant id of this attempt
    admit_s: float  # simulated admission time
    end_s: float  # simulated finish (or kill) time
    report: TransferReport


@dataclasses.dataclass
class FleetReport:
    """Roll-up of a fleet run (per-request reports in request order;
    ``reports[i]`` is request *i*'s final attempt when recovery re-admitted
    it after a kill — ``sessions`` holds every attempt)."""

    reports: list[TransferReport]
    goodput_mbps: float  # aggregate delivered goodput over the makespan
    makespan_s: float
    samples_p50: float  # p50 of per-tenant convergence sample counts
    samples_p99: float
    accuracy_vs_single: float  # mean % of single-tenant optimum steady rate
    reprobe_grants: int
    reprobe_denials: int
    admitted_concurrency: int  # admission cap actually used
    refreshes: int = 0  # continuous-refresh rounds run during the fleet
    refreshed_entries: int = 0  # log entries folded back into the OfflineDB
    kills: int = 0  # sessions interrupted by fault injection
    recoveries: int = 0  # killed sessions re-admitted with residual MB
    sessions: list[SessionOutcome] = dataclasses.field(default_factory=list)

    def attempts_for(self, request_index: int) -> list[SessionOutcome]:
        """Every attempt that served one original request, in order."""
        return [s for s in self.sessions if s.request_index == request_index]


class ReprobeLimiter:
    """Fleet-wide rate limit on mid-transfer re-parameterizations.

    A capacity swing hits every tenant's confidence band at once; letting the
    whole fleet re-probe simultaneously costs N process respawns and another
    capacity swing — the storm this gate damps.  Grants are spaced at least
    ``min_interval_s`` of simulated time apart fleet-wide; a lone tenant is
    never throttled, which keeps N=1 fleets identical to single-tenant runs.
    """

    def __init__(self, min_interval_s: float = 5.0, n_active_fn=None):
        self.min_interval_s = min_interval_s
        self.grants = 0  # guarded-by: _lock
        self.denials = 0  # guarded-by: _lock
        self._n_active_fn = n_active_fn  # called with now_s; tenants live then
        self._last: float | None = None  # guarded-by: _lock
        self._lock = threading.Lock()

    def __call__(self, now_s: float) -> bool:
        with self._lock:
            if self._n_active_fn is not None and self._n_active_fn(now_s) <= 1:
                # Still record the grant time: a tenant admitted right after
                # a lone-tenant grant must not re-probe back-to-back with it.
                self._last = now_s
                self.grants += 1
                return True
            if self._last is None or now_s - self._last >= self.min_interval_s:
                self._last = now_s
                self.grants += 1
                return True
            self.denials += 1
            return False


class _FleetClock:
    """Conservative simulated-time serializer for tenant env interactions.

    A tenant may run a transfer only when its clock is the minimum over all
    admitted, unfinished tenants (ties by id) and no other transfer is in
    flight — the classic conservative discrete-event discipline, which makes
    fleet runs deterministic and contention causally consistent.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._clocks: dict[int, float] = {}  # guarded-by: _lock
        self._admits: dict[int, float] = {}  # guarded-by: _lock
        self._done: set[int] = set()  # guarded-by: _lock
        self._in_flight: int | None = None  # guarded-by: _lock
        self._events: dict[int, threading.Event] = {}  # guarded-by: _lock

    def admit(self, tenant_id: int, clock0: float) -> None:
        with self._lock:
            self._clocks[tenant_id] = clock0
            self._admits[tenant_id] = clock0
            if self._in_flight is None:
                self._wake_next()

    def finish(self, tenant_id: int) -> None:
        with self._lock:
            self._done.add(tenant_id)
            if self._in_flight is None:
                self._wake_next()

    def n_active_at(self, t_s: float) -> int:
        """Tenants whose sessions are live at simulated time ``t_s``: admitted
        by then, and either unfinished or finished with a final clock beyond
        ``t_s`` (their transfers occupy simulated time the asking tenant has
        not reached yet).  A tenant pre-registered with a *future* start does
        not count — a staggered fleet's early tenant is genuinely alone.
        This definition is insensitive to wall-clock finish timing, which
        keeps fleet runs deterministic.
        """
        with self._lock:
            return sum(
                1
                for tid, clk in self._clocks.items()
                if self._admits[tid] <= t_s
                and (tid not in self._done or clk > t_s)
            )

    def _next_up(self):  # holds: _lock
        best = None
        for tid, clk in self._clocks.items():
            if tid not in self._done and (best is None or (clk, tid) < best):
                best = (clk, tid)
        return best

    def _wake_next(self) -> None:  # holds: _lock
        """Wake only the next-up tenant (lock held).  A next-up tenant with
        no registered event has not reached its ``turn`` call yet; its own
        fast path admits it when it does."""
        nxt = self._next_up()
        if nxt is not None:
            ev = self._events.get(nxt[1])
            if ev is not None:
                ev.set()

    @contextlib.contextmanager
    def turn(self, env: TenantEnvironment):
        tid = env.tenant_id
        me = (env.clock_s, tid)
        ev = threading.Event()
        with self._lock:
            self._events[tid] = ev
            if self._in_flight is None and self._next_up() == me:
                ev.set()
        while True:
            ev.wait()
            with self._lock:
                if self._in_flight is None and self._next_up() == me:
                    self._in_flight = tid
                    del self._events[tid]
                    break
                ev.clear()  # stale wake: someone else became next-up first
        try:
            yield
        finally:
            with self._lock:
                self._in_flight = None
                self._clocks[tid] = env.clock_s
                self._wake_next()


def with_link_load(
    requests: list[FleetRequest], testbed: str
) -> list[FleetRequest]:
    """``requests``, each with neither ``traffic`` nor ``constant_load``
    given the link's one external load as its ``traffic``.

    The load is ``netsim.make_link_load(testbed)``, built once per fleet and
    seeded from the ``env_seed`` of the fleet's request 0 (in list order),
    so every tenant of the link reads the same load at the same simulated
    instant, and a fleet's load is a function of its requests alone.
    """
    if all(r.traffic is not None or r.constant_load is not None
           for r in requests):
        return requests
    load = make_link_load(testbed, seed=requests[0].env_seed)
    return [
        r if r.traffic is not None or r.constant_load is not None
        else dataclasses.replace(r, traffic=load)
        for r in requests
    ]


# Single-tenant optima are pure functions of (testbed, seed, load, dataset,
# clock) and cost a 4096-point Python grid search each — memoize fleet-wide so
# benchmark sweeps that score the same requests under several policies pay once.
_OPT_CACHE: dict = {}


def _route_and_score(
    db: OfflineDB,
    requests: list[FleetRequest],
    testbed: str,
    use_pallas: bool,
) -> tuple[np.ndarray, np.ndarray]:
    """The fleet's one routing and scoring pass: ``(demands, clusters)``,
    request *i*'s predicted demand and its nearest cluster's index in
    ``db.clusters`` (float64 ``ClusterModel.assign``, what ``db.query``
    computes).  The whole of it is the trace span ``repro.fleet.score``."""
    link = TESTBEDS[testbed]
    demands = np.zeros(len(requests))
    clusters = np.empty(len(requests), np.int64)
    groups: dict[int, list[int]] = {}
    with obs.span("fleet.score") as span:
        for i, req in enumerate(requests):
            k = db.cluster_model.assign(request_features(link, req.dataset))
            clusters[i] = k
            groups.setdefault(int(k), []).append(i)
        for k, idxs in groups.items():
            stack = db.clusters[k].surface_stack(db.bounds)
            cand = stack.argmax_pts[None, :, :]  # one batch row per cluster
            best, _ = stack.best_candidates(cand, use_pallas=use_pallas)
            demands[idxs] = float(np.asarray(best)[0, stack.n_surfaces // 2])
        span.set_metadata(clusters=len(groups))
    return demands, clusters


def predict_demands(
    db: OfflineDB,
    requests: list[FleetRequest],
    *,
    testbed: str = "xsede",
    use_pallas: bool = False,
) -> np.ndarray:
    """Predicted per-request demand (Mbit/s) via the batched surface path.

    Requests are grouped by cluster and each cluster's surface stack is
    scored through ``SurfaceStack.best_candidates`` (vmapped gather or the
    Pallas kernel).  Demand is a pure function of the cluster — the
    candidate set is the cluster's own argmax points — so each group is
    scored once and broadcast to its requests.  The median-load surface's
    best candidate is what the admission controller budgets against.  The
    whole of it, routing and scoring up to the host's read of each result,
    is the trace span ``repro.fleet.score``.
    """
    return _route_and_score(db, requests, testbed, use_pallas)[0]


class AdmissionPlan(NamedTuple):
    """The admission cap and the routing it was computed from."""

    cap: int
    clusters: np.ndarray  # int64: request i's cluster index in db.clusters


def auto_concurrency(
    db: OfflineDB,
    requests: list[FleetRequest],
    link,
    *,
    testbed: str = "xsede",
    overcommit: float = 2.0,
    use_pallas: bool = False,
) -> AdmissionPlan:
    """Admission cap from predicted demand: how many median-demand sessions
    fit under the link's capacity times ``overcommit``; with it each
    request's cluster from the same pass, for engines whose knowledge is
    frozen for the run to admit without routing again."""
    demands, clusters = _route_and_score(db, requests, testbed, use_pallas)
    med = float(np.median(demands))
    if med <= 0.0:
        return AdmissionPlan(len(requests), clusters)
    cap = int(overcommit * link.bandwidth_mbps / med)
    return AdmissionPlan(max(1, min(cap, len(requests))), clusters)


def single_tenant_optimum(
    db: OfflineDB, testbed: str, req: FleetRequest, at_clock_s: float
) -> float:
    """Steady rate of the grid-search optimum a lone tenant would achieve on
    a fresh testbed at ``at_clock_s`` (memoized in ``_OPT_CACHE``)."""
    ds = req.dataset
    # db.bounds must key the memo: the optimum is a grid search over the
    # db's parameter domain, and the DET103 taint audit showed two
    # differently-bounded DBs in one process would otherwise share entries.
    key = (
        db.bounds,
        testbed,
        req.env_seed,
        req.constant_load,
        req.traffic,
        ds,
        at_clock_s,
    )
    if key not in _OPT_CACHE:
        if req.traffic is not None:
            env = Environment(TESTBEDS[testbed], req.traffic, seed=req.env_seed)
        else:
            env = make_testbed(
                testbed,
                seed=req.env_seed,
                constant_load=req.constant_load,
            )
        env.clock_s = at_clock_s
        _, opt = env.optimal(db.bounds, ds.avg_file_mb, ds.n_files)
        _OPT_CACHE[key] = opt
    return _OPT_CACHE[key]


def assemble_fleet_report(
    db: OfflineDB,
    testbed: str,
    requests: list[FleetRequest],
    *,
    reqs: list[FleetRequest],
    origin: list[int],
    attempt_no: list[int],
    reports: list[TransferReport | None],
    end_clock: list[float],
    admit_time: list[float],
    score_vs_single: bool,
    reprobe_grants: int,
    reprobe_denials: int,
    admitted_concurrency: int,
    refreshes: int = 0,
    refreshed_entries: int = 0,
    kills: int = 0,
    recoveries: int = 0,
) -> FleetReport:
    """Roll attempt-indexed session state up into a ``FleetReport``.

    Shared verbatim by the threaded scheduler and the vectorized engine so
    both aggregate with an identical float-operation order — the oracle
    parity guarantee covers the roll-up, not just the sessions.
    """
    n = len(requests)
    # Final report per original request = its last attempt (attempts for
    # one request are appended in order, so a later slot wins).
    final: dict[int, int] = {}
    for j in range(len(reqs)):
        if reports[j] is not None:
            final[origin[j]] = j
    done = [reports[final[i]] for i in range(n) if i in final]
    all_reports = [r for r in reports if r is not None]
    t_start = min(admit_time[:n])
    makespan = max(end_clock) - t_start
    moved_mb = sum(r.moved_mb for r in all_reports)
    samples = np.array([r.n_samples for r in all_reports], np.float64)
    if score_vs_single:
        accs = []
        for i in range(n):
            if i not in final:
                continue
            opt = single_tenant_optimum(db, testbed, requests[i], admit_time[i])
            accs.append(
                100.0 * min(reports[final[i]].steady_mbps, opt) / max(opt, 1e-9)
            )
        accuracy = float(np.mean(accs)) if accs else 0.0
    else:
        accuracy = float("nan")
    sessions = [
        SessionOutcome(
            request_index=origin[j],
            attempt=attempt_no[j],
            tenant_id=j,
            admit_s=admit_time[j],
            end_s=end_clock[j],
            report=reports[j],
        )
        for j in range(len(reqs))
        if reports[j] is not None
    ]
    return FleetReport(
        reports=done,
        goodput_mbps=moved_mb * 8.0 / max(makespan, 1e-9),
        makespan_s=makespan,
        samples_p50=float(np.percentile(samples, 50)),
        samples_p99=float(np.percentile(samples, 99)),
        accuracy_vs_single=accuracy,
        reprobe_grants=reprobe_grants,
        reprobe_denials=reprobe_denials,
        admitted_concurrency=admitted_concurrency,
        refreshes=refreshes,
        refreshed_entries=refreshed_entries,
        kills=kills,
        recoveries=recoveries,
        sessions=sessions,
    )


class FleetScheduler:
    """Run N concurrent ``AdaptiveSampler`` sessions against one shared link."""

    def __init__(
        self,
        db: OfflineDB,
        *,
        z: float = 2.0,
        max_samples: int = 3,
        bulk_chunks: int = 8,
        config: FleetConfig | None = None,
        use_pallas: bool = False,
        knowledge=None,
    ):
        self.db = db
        self.z = z
        self.max_samples = max_samples
        self.bulk_chunks = bulk_chunks
        self.config = config or FleetConfig()
        self.use_pallas = use_pallas
        # Optional core.service.KnowledgeService (duck-typed to keep this
        # module service-import-free).  When set it replaces the refresher:
        # admission snapshots, session fold-in, and probe budgets all route
        # through the service; None keeps the legacy path bit-identical.
        self.knowledge = knowledge
        if knowledge is not None and knowledge.db_for(None) is not db:
            raise ValueError(
                "knowledge service must serve the same OfflineDB the "
                "scheduler runs against"
            )

    # ------------------------------------------------------------------ #
    # contention-aware admission
    # ------------------------------------------------------------------ #
    def predict_demands(self, requests: list[FleetRequest]) -> np.ndarray:
        """Per-request demand via the module-level :func:`predict_demands`."""
        return predict_demands(
            self.db,
            requests,
            testbed=self.config.testbed,
            use_pallas=self.use_pallas,
        )

    def _auto_concurrency(self, requests: list[FleetRequest], link) -> int:
        return auto_concurrency(
            self.db,
            requests,
            link,
            testbed=self.config.testbed,
            overcommit=self.config.overcommit,
            use_pallas=self.use_pallas,
        ).cap

    # ------------------------------------------------------------------ #
    def _make_tenant_env(
        self, req: FleetRequest, tenant_id: int, shared: SharedLink, clock
    ) -> TenantEnvironment:
        base = make_testbed(
            self.config.testbed,
            seed=req.env_seed,
            constant_load=req.constant_load,
        )
        traffic = req.traffic if req.traffic is not None else base.traffic
        return TenantEnvironment(
            base.link,
            traffic,
            shared,
            tenant_id,
            noise_sigma=base.noise_sigma,
            seed=req.env_seed,
            turn_gate=clock.turn,
            faults=self.config.faults,
        )

    def _single_tenant_optimum(self, req: FleetRequest, at_clock_s: float) -> float:
        return single_tenant_optimum(self.db, self.config.testbed, req, at_clock_s)

    # ------------------------------------------------------------------ #
    def run(self, requests: list[FleetRequest]) -> FleetReport:
        n = len(requests)
        if n == 0:
            return FleetReport([], 0.0, 0.0, 0.0, 0.0, 0.0, 0, 0, 0)
        requests = with_link_load(requests, self.config.testbed)
        link = TESTBEDS[self.config.testbed]
        shared = SharedLink(link)
        clock = _FleetClock()
        limiter = ReprobeLimiter(
            self.config.reprobe_interval_s, n_active_fn=clock.n_active_at
        )
        knowledge = self.knowledge
        refresher = (
            KnowledgeRefresher(self.db, link, self.config.refresh)
            if self.config.refresh is not None and knowledge is None
            else None
        )
        # Service counters are cumulative across runs; report the delta.
        k_stats0 = knowledge.stats() if knowledge is not None else None
        cap = self.config.max_concurrent or self._auto_concurrency(requests, link)
        recovery = self.config.recovery

        # Attempt-indexed state.  Slots 0..n-1 are the original requests'
        # first attempts; recovery re-admissions of killed sessions append
        # further slots (list growth only ever happens under admit_lock, and
        # existing indices are never moved, so workers may read their own
        # slot lock-free).
        reqs: list[FleetRequest] = list(requests)
        origin = list(range(n))  # attempt -> original request index
        attempt_no = [0] * n
        reports: list[TransferReport | None] = [None] * n
        end_clock = [0.0] * n
        admit_time = [0.0] * n
        # Knowledge snapshot per tenant, resolved at admission: admissions
        # happen either before any worker runs (the initial wave) or inside a
        # finishing tenant's serialized turn, i.e. in simulated-time order —
        # so under continuous refresh every session still gets a
        # deterministic, fully-consistent cluster, instead of racing its
        # wall-clock db.query against a concurrent refit swap.
        admitted_cluster = [None] * n
        # Probe budget per attempt, resolved at admission (same serialized
        # point as the knowledge snapshot) so backoff decisions land in
        # simulated-time order; without a service this is a constant.
        admit_budget = [self.max_samples] * n
        admit_events = [threading.Event() for _ in range(n)]
        threads: list[threading.Thread] = []  # guarded-by: admit_lock
        pending = collections.deque(  # guarded-by: admit_lock
            sorted(range(n), key=lambda i: (reqs[i].start_clock_s, i))
        )
        admit_lock = threading.Lock()
        errors: list[BaseException] = []
        n_kills = [0]  # guarded-by: admit_lock
        n_recoveries = [0]  # guarded-by: admit_lock

        def admit_next(now_s: float) -> None:
            with admit_lock:
                if not pending:
                    return
                i = pending.popleft()
                admit_time[i] = max(reqs[i].start_clock_s, now_s)
                feats = request_features(link, reqs[i].dataset)
                if knowledge is not None:
                    # Same snapshot object db.query would return (the
                    # service routes through the same cluster model), plus
                    # the backoff policy's probe budget for this admission.
                    admitted_cluster[i] = knowledge.query_cluster(None, feats)
                    admit_budget[i] = knowledge.probe_budget(
                        None, admit_time[i], self.max_samples
                    )
                else:
                    admitted_cluster[i] = self.db.query(feats)
                # Register with the fleet clock BEFORE releasing the worker:
                # from this point every already-running tenant waits for i
                # whenever i's clock is the fleet minimum, even if i's thread
                # has not been scheduled yet.
                clock.admit(i, admit_time[i])
                admit_events[i].set()

        def enqueue_recovery(i: int, now_s: float) -> None:
            """Re-admit attempt ``i``'s killed session with its residual
            bytes.  Runs inside the dying worker's serialized turn, so
            re-admissions land in simulated-time kill order and the fleet
            stays deterministic."""
            rep = reports[i]
            if rep is None or not rep.interrupted:
                return
            with admit_lock:
                n_kills[0] += 1
                if (
                    recovery is None
                    or attempt_no[i] >= recovery.max_restarts
                    or rep.moved_mb >= reqs[i].dataset.total_mb - 1e-9
                ):
                    return
                n_recoveries[0] += 1
                nxt = dataclasses.replace(
                    reqs[i],
                    dataset=reqs[i].dataset.residual(rep.moved_mb),
                    start_clock_s=now_s + recovery.restart_delay_s,
                    env_seed=reqs[i].env_seed + 101,
                )
                j = len(reqs)
                reqs.append(nxt)
                origin.append(origin[i])
                attempt_no.append(attempt_no[i] + 1)
                reports.append(None)
                end_clock.append(0.0)
                admit_time.append(0.0)
                admitted_cluster.append(None)
                admit_budget.append(self.max_samples)
                admit_events.append(threading.Event())
                pending.append(j)
                th = threading.Thread(target=worker, args=(j,), daemon=True)
                threads.append(th)
                th.start()  # blocks on admit_events[j] until admitted

        def worker(i: int) -> None:
            admit_events[i].wait()
            env: TenantEnvironment | None = None
            try:
                env = self._make_tenant_env(reqs[i], i, shared, clock)
                env.clock_s = admit_time[i]  # already registered by admit_next

                def gate(now_s: float, _env=env) -> bool:
                    # Serialize limiter decisions in simulated-time order,
                    # like transfers: unordered wall-clock races between
                    # tenants' grant requests would break determinism.
                    with clock.turn(_env):
                        return limiter(now_s)

                sampler = AdaptiveSampler(
                    self.db,
                    z=self.z,
                    max_samples=admit_budget[i],
                    bulk_chunks=self.bulk_chunks,
                    reprobe_gate=gate,
                    recovery=recovery,
                )
                reports[i] = sampler.transfer(
                    env, reqs[i].dataset, cluster=admitted_cluster[i]
                )
            except BaseException as e:  # surfaced after join
                errors.append(e)
            finally:
                # clock.finish must run on EVERY exit path — a tenant that
                # dies registered-but-unfinished deadlocks the whole fleet.
                now = env.clock_s if env is not None else admit_time[i]
                end_clock[i] = now
                # Take one last serialized turn before retiring: queued
                # admissions must follow simulated-time finish order, not
                # wall-clock thread-scheduling order.  The finished tenant's
                # last flow interval stays registered on the shared link —
                # it still occupies simulated time other tenants have not
                # reached — and expires by its own end time (a killed
                # session's interval was already truncated at the kill
                # instant by the environment).  Continuous refresh folds the
                # finished session in inside this same turn, so refreshes
                # too land in simulated-time finish order and queued
                # admissions snapshot post-refresh knowledge.  Interrupted
                # sessions are excluded because a kill-truncated trace is
                # not a set of steady-state observations; *completed*
                # sessions fold in even when a fault was active — learning
                # the link as it currently behaves, degraded or not, is
                # what continuous refresh is for (the additive update
                # re-learns the healthy regime as post-fault sessions land).
                if env is not None:
                    with clock.turn(env):
                        rep = reports[i]
                        if knowledge is not None and rep is not None:
                            # The service handles interrupted/collapsed
                            # sessions itself (fault signal, no fold-in).
                            knowledge.observe(
                                rep, reqs[i].dataset, link=link, now_s=now
                            )
                        elif (
                            refresher is not None
                            and rep is not None
                            and not rep.interrupted
                        ):
                            refresher.observe(rep, reqs[i].dataset, now_s=now)
                        enqueue_recovery(i, now)
                        admit_next(now)
                else:
                    admit_next(now)
                clock.finish(i)

        for i in range(n):
            threads.append(threading.Thread(target=worker, args=(i,), daemon=True))
        # Admit (and clock-register) the whole initial wave BEFORE any worker
        # thread can run: a first tenant racing ahead of the second tenant's
        # registration would escape serialization entirely.
        for _ in range(min(cap, n)):
            admit_next(float("-inf"))
        for i in range(n):
            threads[i].start()
        joined = 0
        while True:
            with admit_lock:
                if joined >= len(threads):
                    break
                th = threads[joined]
            th.join()
            joined += 1
        if errors:
            raise errors[0]

        if knowledge is not None:
            k_stats = knowledge.stats()
            n_refreshes = k_stats.refits - k_stats0.refits
            n_refreshed = k_stats.entries_folded - k_stats0.entries_folded
        else:
            n_refreshes = refresher.refreshes if refresher is not None else 0
            n_refreshed = (
                refresher.entries_folded if refresher is not None else 0
            )
        return assemble_fleet_report(
            self.db,
            self.config.testbed,
            requests,
            reqs=reqs,
            origin=origin,
            attempt_no=attempt_no,
            reports=reports,
            end_clock=end_clock,
            admit_time=admit_time,
            score_vs_single=self.config.score_vs_single,
            reprobe_grants=limiter.grants,
            reprobe_denials=limiter.denials,
            admitted_concurrency=min(cap, n),
            refreshes=n_refreshes,
            refreshed_entries=n_refreshed,
            kills=n_kills[0],
            recoveries=n_recoveries[0],
        )
