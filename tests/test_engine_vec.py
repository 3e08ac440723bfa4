"""Event-loop fleet engines vs the threaded oracle: bit-identical
``FleetReport``s across fleet sizes, fault classes, refresh, and staggered
admission — plus the admission path (one routing pass per fleet, one
environment per admission) and unit coverage of the engine's state arrays
and the incremental active-session counter."""

import hashlib
import json
import pathlib
import sys

import numpy as np
import pytest

from repro import obs
from repro.core import (
    EngineConfig,
    FleetRequest,
    RecoveryConfig,
    RefreshConfig,
    run_fleet,
)
from repro.core.engine import VectorizedFleetEngine
from repro.core.engine.vectorized import (
    PHASE_IDLE,
    FleetStateArrays,
    _ActiveCounter,
)
from repro.core.online import AdaptiveSampler, request_features
from repro.core.service import KnowledgeService
from repro.netsim import FaultSchedule, make_dataset
from repro.netsim.environment import Environment
from repro.netsim.faults import TenantKill
from repro.netsim.testbeds import TESTBEDS
from repro.testing import (
    SCENARIO_MATRIX,
    build_scenario_db,
    canonical_trace,
    run_scenario,
)

START = 4 * 3600.0

#: The event-loop engines held to the threaded oracle, as ``EngineConfig``
#: settings: the vectorized engine and the sharded engine's strict regime.
ENGINES = {
    "vectorized": dict(engine="vectorized"),
    "sharded": dict(engine="sharded", n_shards=2, shard_window_s=0.0),
}
#: The sharded engine's windowed regime: oracle-exact only where one
#: session runs at a time under a constant load, since it freezes
#: contention and load per window.
WINDOWED = dict(engine="sharded", n_shards=2, shard_window_s=120.0)


@pytest.fixture(scope="module")
def dbs():
    return {
        tb: build_scenario_db(tb)
        for tb in sorted({sc.testbed for sc in SCENARIO_MATRIX})
    }


def _requests(n, *, stagger=0.0, seed0=99, size="medium"):
    return [
        FleetRequest(
            dataset=make_dataset(size, 7 + i),
            env_seed=seed0 + i,
            start_clock_s=START + stagger * i,
        )
        for i in range(n)
    ]


def _both(db, reqs, engine="vectorized", **kw):
    """The threaded oracle's report and ``engine``'s (a key of
    ``ENGINES``, or ``"windowed"``) on the same fleet."""
    threaded = run_fleet(db, reqs, EngineConfig(engine="threaded", **kw))
    settings = WINDOWED if engine == "windowed" else ENGINES[engine]
    return threaded, run_fleet(db, reqs, EngineConfig(**settings, **kw))


# ------------------------------------------------------------------ #
# parity with the threaded oracle
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("engine", ["vectorized", "sharded"])
@pytest.mark.parametrize(
    "name",
    [
        "xsede-3-none-constant",
        "xsede-3-drop-constant",
        "xsede-3-kill-constant",
        "xsede-3-churn-constant",
        "didclab-xsede-3-kill-constant",
    ],
)
def test_matrix_cells_bit_identical_across_engines(dbs, name, engine):
    sc = next(s for s in SCENARIO_MATRIX if s.name == name)
    threaded = run_scenario(dbs[sc.testbed], sc, engine="threaded")
    vectorized = run_scenario(dbs[sc.testbed], sc, engine=engine)
    assert canonical_trace(vectorized) == canonical_trace(threaded)
    assert vectorized == threaded  # bit-for-bit, not approx


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("n", [1, 8, 32])
def test_fault_free_parity_across_fleet_sizes(dbs, n, engine):
    threaded, vectorized = _both(
        dbs["xsede"], _requests(n), engine, max_concurrent=min(n, 8)
    )
    assert vectorized == threaded
    assert len(vectorized.reports) == n


@pytest.mark.parametrize("engine", ENGINES)
def test_parity_with_auto_concurrency_and_staggered_starts(dbs, engine):
    # max_concurrent=None exercises the batched-prediction auto cap; the
    # stagger makes admission times distinct so queue ordering matters.
    threaded, vectorized = _both(dbs["xsede"], _requests(8, stagger=7.0), engine)
    assert vectorized == threaded


@pytest.mark.parametrize("engine", [*ENGINES, "windowed"])
def test_one_at_a_time_parity_on_the_fleets_routing(dbs, engine):
    # An overcommit this small caps the fleet at one session, so every
    # engine, the windowed regime too, runs one session at a time under
    # the constant load; the cap is computed, so first attempts are
    # admitted on the routing of that pass.
    reqs = [
        FleetRequest(
            dataset=make_dataset(("small", "medium", "large")[i % 3], 7 + i),
            env_seed=99 + i,
            start_clock_s=START,
            constant_load=0.15,
        )
        for i in range(6)
    ]
    threaded, other = _both(dbs["xsede"], reqs, engine, overcommit=1e-3)
    assert threaded.admitted_concurrency == 1
    assert other == threaded


@pytest.mark.parametrize("engine", ENGINES)
def test_faulted_parity_with_recovery_at_n8(dbs, engine):
    faults = FaultSchedule.generate(
        17,
        start_s=START,
        horizon_s=90.0,
        n_flaps=0,
        n_drops=1,
        n_bursts=0,
        n_kills=3,
        n_tenants=8,
    )
    threaded, vectorized = _both(
        dbs["xsede"],
        _requests(8),
        engine,
        max_concurrent=4,
        faults=faults,
        recovery=RecoveryConfig(),
    )
    assert vectorized == threaded
    assert vectorized.recoveries >= 1  # the fault actually bit


@pytest.mark.parametrize("engine", ENGINES)
def test_refresh_parity_uses_fresh_dbs_per_engine(engine):
    # The refresher mutates the DB in place, so each engine gets its own
    # identically-built copy; parity then covers the refresh path too.
    reqs = _requests(8)
    kw = dict(
        max_concurrent=4,
        refresh=RefreshConfig(every_completions=2, min_entries=4),
    )
    threaded = run_fleet(
        build_scenario_db("xsede"), reqs, EngineConfig(engine="threaded", **kw)
    )
    vectorized = run_fleet(
        build_scenario_db("xsede"),
        reqs,
        EngineConfig(**ENGINES[engine], **kw),
    )
    assert vectorized == threaded
    assert vectorized.refreshes >= 1


# ------------------------------------------------------------------ #
# admission: the fleet's one routing pass, one environment per tenant
# ------------------------------------------------------------------ #
def _kills(n_kills):
    """First-wave tenants 0..n_kills-1 killed 30 s into the fleet."""
    return FaultSchedule(
        tuple(TenantKill(START + 30.0 + 10.0 * k, k) for k in range(n_kills))
    )


#: Each admission case: its ``EngineConfig`` settings, its fleet size
#: (small where knowledge is refit as sessions finish), and whether first
#: attempts take the fleet's routing in it: only where the cap is computed
#: and the knowledge is frozen for the run.
ADMISSION_CASES = {
    "frozen": (lambda db: {}, 300, True),
    "kill-recovery": (
        lambda db: dict(faults=_kills(3), recovery=RecoveryConfig()),
        300,
        True,
    ),
    "fixed-cap": (lambda db: dict(max_concurrent=4), 300, False),
    "refresh": (
        lambda db: dict(refresh=RefreshConfig(every_completions=2, min_entries=4)),
        8,
        False,
    ),
    "service": (lambda db: dict(knowledge=KnowledgeService(db)), 8, False),
}


@pytest.mark.parametrize("case", ADMISSION_CASES)
@pytest.mark.parametrize("testbed", ["xsede", "didclab"])
def test_first_attempts_take_the_fleets_routing_while_knowledge_is_frozen(
    testbed, case, monkeypatch
):
    db = build_scenario_db(testbed)  # fresh: refresh and the service refit
    settings, n, prerouted = ADMISSION_CASES[case]
    sizes = ("small", "medium", "large")
    reqs = [
        FleetRequest(
            dataset=make_dataset(sizes[i % 3], 7 + i),
            env_seed=99 + i,
            start_clock_s=START,
        )
        for i in range(n)
    ]
    admitted = []  # (slot, dataset, cluster) of every admission
    session = AdaptiveSampler.session

    def recording(self, env, dataset, cluster):
        admitted.append((env.tenant_id, dataset, cluster))
        return session(self, env, dataset, cluster)

    monkeypatch.setattr(AdaptiveSampler, "session", recording)
    # the engine keeps its counters as with a profiler session open,
    # without the profiler's cost (tests/test_obs.py reads them traced)
    monkeypatch.setattr(obs, "active", lambda: True)
    engine = VectorizedFleetEngine(
        db,
        EngineConfig(
            engine="vectorized",
            testbed=testbed,
            score_vs_single=False,
            **settings(db),
        ),
    )
    report = engine.run(reqs)
    assert len(admitted) == n + report.recoveries
    assert engine.counters["admissions"] == len(admitted)
    assert engine.counters["prerouted"] == (n if prerouted else 0)
    if case == "kill-recovery":
        assert report.recoveries >= 1  # re-admissions, which query
    if case == "refresh":
        assert report.refreshes >= 1
    if case in ("refresh", "service"):
        return  # the knowledge moved during the run: no later query holds
    link = TESTBEDS[testbed]
    assert len({id(c) for _, _, c in admitted}) > 1  # routing has a choice
    for _, dataset, cluster in admitted:
        assert cluster is db.query(request_features(link, dataset))


@pytest.mark.parametrize("engine", [*ENGINES, "windowed"])
def test_one_environment_per_admission(dbs, engine, monkeypatch):
    built = 0
    init = Environment.__init__

    def counting(self, *args, **kwargs):
        nonlocal built
        built += 1
        init(self, *args, **kwargs)

    monkeypatch.setattr(Environment, "__init__", counting)
    settings = WINDOWED if engine == "windowed" else ENGINES[engine]
    report = run_fleet(
        dbs["xsede"],
        _requests(50),
        EngineConfig(
            **settings,
            score_vs_single=False,
            faults=_kills(3),
            recovery=RecoveryConfig(),
        ),
    )
    assert report.recoveries >= 1
    assert built == 50 + report.recoveries


def test_indexed_contention_close_to_exact(dbs):
    reqs = _requests(8)
    kw = dict(max_concurrent=8, score_vs_single=False)
    exact = run_fleet(
        dbs["xsede"],
        reqs,
        EngineConfig(engine="vectorized", contention="exact", **kw),
    )
    indexed = run_fleet(
        dbs["xsede"],
        reqs,
        EngineConfig(engine="vectorized", contention="indexed", **kw),
    )
    # Different float-summation order, same physics: per-session goodput
    # must agree tightly even though traces need not be bit-identical.
    for a, b in zip(exact.reports, indexed.reports):
        assert b.achieved_mbps == pytest.approx(a.achieved_mbps, rel=1e-6)
    assert indexed.goodput_mbps == pytest.approx(exact.goodput_mbps, rel=1e-6)


# ------------------------------------------------------------------ #
# the benchmark cells' traffic: reports pinned by recorded hashes
# ------------------------------------------------------------------ #
#: md5 of ``repr(report)`` of a 200-request vectorized fleet of each
#: cell's traffic, ``(traffic, seed, contention)``, recorded with the law
#: computed whole on every chunk and one scalar noise draw per chunk.
CELL_FLEET_MD5 = {
    ("fleet-2k", 11, "exact"): "1692c20d656461f2a5d8294b49e0eab6",
    ("fleet-2k", 22, "exact"): "cf76346046758128ab28aeac1b673e65",
    ("fleet-2k", 33, "exact"): "24c11c8f9e65dd80523dac73e3a30351",
    ("poisson-2k", 11, "exact"): "acb47300f336bb02b386f0b472ad6934",
    ("poisson-2k", 22, "exact"): "cb25b3329d258469453f38586c62b6da",
    ("poisson-2k", 33, "exact"): "682e93407ef67233eba8bd88e2d095f3",
    ("fleet-2k", 11, "indexed"): "abeeeab202a5e0770d6383749f919bba",
    ("fleet-2k", 22, "indexed"): "7d96152012bcc672c0fcf2787d171dc3",
    ("fleet-2k", 33, "indexed"): "24a351b96b0169af5b60e4e908eb8480",
    ("poisson-2k", 11, "indexed"): "738ebb8d61a0d7c78ed525e195164b6b",
    ("poisson-2k", 22, "indexed"): "1f4e9b43f5d240fdeab399b894d4cbc8",
    ("poisson-2k", 33, "indexed"): "47779ab10a0ccc0dd1074e2b1f026cd0",
}
#: each traffic's configuration in ``benchmarks/chip/configs``
CELL_CONFIG = {"fleet-2k": "xsede-fleet", "poisson-2k": "didclab-lan"}


@pytest.fixture(scope="module")
def cell_fleet():
    """``(traffic, seed, contention) -> (db, requests, config)``: a
    200-request fleet drawn by the benchmark's fleet driver from the
    cell's configuration and traffic, on a scenario DB of its testbed."""
    root = pathlib.Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from benchmarks.chip.drivers import fleet

    chip = root / "benchmarks" / "chip"
    built = {}

    def make(traffic, seed, contention):
        config = json.loads(
            (chip / "configs" / f"{CELL_CONFIG[traffic]}.json").read_text()
        )
        mix = json.loads((chip / "traffic" / f"{traffic}.json").read_text())
        testbed = config["testbed"]
        if testbed not in built:
            built[testbed] = build_scenario_db(testbed)
        return (
            built[testbed],
            fleet.requests(config, dict(mix, sessions=200), seed),
            fleet.engine_config(config, contention=contention),
        )

    return make


@pytest.mark.parametrize("key", sorted(CELL_FLEET_MD5), ids=lambda k: "-".join(map(str, k)))
def test_cell_traffic_reports_match_recorded_hashes(cell_fleet, key):
    traffic, seed, contention = key
    report = run_fleet(*cell_fleet(traffic, seed, contention))
    assert len(report.sessions) == 200
    digest = hashlib.md5(repr(report).encode()).hexdigest()
    assert digest == CELL_FLEET_MD5[key]


# ------------------------------------------------------------------ #
# engine internals
# ------------------------------------------------------------------ #
def test_engine_state_retires_every_slot(dbs):
    engine = VectorizedFleetEngine(
        dbs["xsede"], EngineConfig(engine="vectorized", max_concurrent=2)
    )
    fleet = engine.run(_requests(4))
    assert len(fleet.reports) == 4
    assert engine.events_processed > 0
    hist = engine.state.live_histogram(4)
    assert hist == {PHASE_IDLE: 4}  # every slot retired back to idle


def test_state_arrays_grow_preserving_contents():
    st = FleetStateArrays.allocate(2)
    st.phase[1] = 3
    st.params[1] = (4, 8, 2)
    st.next_event_s[1] = 123.5
    st.grow_to(9)
    assert st.phase.shape[0] >= 9
    assert st.params.shape == (st.phase.shape[0], 3)
    assert st.phase[1] == 3
    assert tuple(st.params[1]) == (4, 8, 2)
    assert st.next_event_s[1] == 123.5
    assert np.all(np.isinf(st.next_event_s[2:]))  # new rows start inert
    before = st.phase.shape[0]
    st.grow_to(4)  # never shrinks
    assert st.phase.shape[0] == before


def test_active_counter_matches_brute_force():
    rng = np.random.default_rng(5)
    admits = np.sort(rng.uniform(0.0, 50.0, size=40))
    counter = _ActiveCounter()
    for t in admits:
        counter.admit(float(t))
    n_finished = 0
    # queries arrive in event order (monotone time), like the engine loop
    for step, now in enumerate(np.linspace(0.0, 80.0, 161)):
        want = int(np.sum(admits <= now)) - n_finished
        assert counter(float(now)) == want
        if step % 5 == 4 and want > 0:  # retire one active session
            counter.finish(float(now))
            n_finished += 1
    assert n_finished > 0
    assert counter(100.0) == len(admits) - n_finished
