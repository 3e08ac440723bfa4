"""Sharded fleet engine vs the vectorized oracle: the strict regime must be
bit-identical across the full scenario matrix (recovery on and off) and
across fleet sizes, the windowed scale regime must be deterministic and
physically close to strict, and the frontier / partition / config plumbing
gets direct unit coverage."""

import json
import math
import pathlib

import numpy as np
import pytest

from repro.core import (
    EngineConfig,
    FleetRequest,
    KnowledgeService,
    RecoveryConfig,
    RefreshConfig,
    ServiceConfig,
    run_fleet,
)
from repro.core.engine import (
    DEFAULT_SHARD_WINDOW_S,
    ShardedEventFrontier,
    ShardedFleetEngine,
)
from repro.core.engine.heap import VectorEventHeap
from repro.core.engine.shard import WindowedLinkState
from repro.dist.sharding import slot_partition, slot_shard
from repro.netsim import FaultSchedule, make_dataset
from repro.netsim.environment import IndexedSharedLink
from repro.netsim.testbeds import TESTBEDS
from repro.testing import (
    SCENARIO_MATRIX,
    build_scenario_db,
    canonical_trace,
    run_scenario,
)

START = 4 * 3600.0
GOLDEN = pathlib.Path(__file__).parent / "golden"


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


def _both(db, reqs, *, shard_kw=None, **kw):
    vec = run_fleet(db, reqs, EngineConfig(engine="vectorized", **kw))
    shd = run_fleet(
        db, reqs, EngineConfig(engine="sharded", **(shard_kw or {}), **kw)
    )
    return vec, shd


# ------------------------------------------------------------------ #
# strict regime: bit-identical to the vectorized oracle
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("recovery", [False, True], ids=["norec", "rec"])
@pytest.mark.parametrize("sc", SCENARIO_MATRIX, ids=lambda sc: sc.name)
def test_full_matrix_bit_identical_to_vectorized(dbs, sc, recovery):
    vec = run_scenario(dbs[sc.testbed], sc, recovery=recovery,
                       engine="vectorized")
    shd = run_scenario(dbs[sc.testbed], sc, recovery=recovery,
                       engine="sharded")
    assert canonical_trace(shd) == canonical_trace(vec)
    assert shd == vec  # bit-for-bit, not approx


@pytest.mark.parametrize("n", [8, 1024])
def test_parity_across_fleet_sizes(dbs, n):
    # 1024 sits at (not above) the contention cutover, so the sharded
    # engine auto-selects the strict regime; accuracy scoring is NaN with
    # score_vs_single=False, so compare trace + reports + real scalars.
    kw = dict(max_concurrent=min(n, 64), score_vs_single=False)
    reqs = [
        FleetRequest(
            dataset=make_dataset("small", 7 + i),
            env_seed=99 + i,
            start_clock_s=START,
            constant_load=0.15,
        )
        for i in range(n)
    ]
    vec, shd = _both(dbs["xsede"], reqs, **kw)
    assert canonical_trace(shd) == canonical_trace(vec)
    assert shd.reports == vec.reports
    assert shd.goodput_mbps == vec.goodput_mbps
    assert shd.makespan_s == vec.makespan_s
    assert len(shd.reports) == n


def test_faulted_parity_with_recovery(dbs):
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
    vec, shd = _both(
        dbs["xsede"],
        _requests(8),
        max_concurrent=4,
        faults=faults,
        recovery=RecoveryConfig(),
    )
    assert shd == vec
    assert shd.recoveries >= 1  # the fault actually bit


def test_refresh_parity_uses_fresh_dbs_per_engine():
    # The refresher mutates the DB in place, so each engine gets its own
    # identically-built copy; parity then covers the refresh path too (the
    # sharded engine must not precompute admissions when refresh is on).
    reqs = _requests(8)
    kw = dict(
        max_concurrent=4,
        refresh=RefreshConfig(every_completions=2, min_entries=4),
    )
    vec = run_fleet(
        build_scenario_db("xsede"), reqs, EngineConfig(engine="vectorized", **kw)
    )
    shd = run_fleet(
        build_scenario_db("xsede"), reqs, EngineConfig(engine="sharded", **kw)
    )
    assert shd == vec
    assert shd.refreshes >= 1


def test_single_shard_matches_vectorized(dbs):
    vec, shd = _both(
        dbs["xsede"], _requests(6), shard_kw=dict(n_shards=1), max_concurrent=3
    )
    assert shd == vec


# ------------------------------------------------------------------ #
# windowed scale regime: deterministic, close to strict
# ------------------------------------------------------------------ #
def _scale_requests(n, *, seed0=500):
    classes = ("small", "medium", "large")
    return [
        FleetRequest(
            dataset=make_dataset(classes[i % 3], 30 + i),
            env_seed=seed0 + i,
            start_clock_s=START,
            constant_load=0.15,
        )
        for i in range(n)
    ]


def _windowed_pair(db, n=256, window=120.0, **kw):
    reqs = _scale_requests(n)
    strict = run_fleet(
        db,
        reqs,
        EngineConfig(
            engine="sharded", n_shards=4, shard_window_s=0.0,
            max_concurrent=8, score_vs_single=False, **kw,
        ),
    )
    windowed = run_fleet(
        db,
        reqs,
        EngineConfig(
            engine="sharded", n_shards=4, shard_window_s=window,
            max_concurrent=8, score_vs_single=False, **kw,
        ),
    )
    return reqs, strict, windowed


def test_windowed_regime_deterministic(dbs):
    _, _, a = _windowed_pair(dbs["xsede"])
    _, _, b = _windowed_pair(dbs["xsede"])
    assert canonical_trace(a) == canonical_trace(b)
    assert a.reports == b.reports
    assert a.goodput_mbps == b.goodput_mbps


def test_windowed_close_to_strict(dbs):
    reqs, strict, windowed = _windowed_pair(dbs["xsede"])
    # One coarsening level (frozen per-window contention and load) must
    # stay physically faithful: same sessions, every byte delivered, and
    # aggregate goodput/makespan within a tight band of the strict run.
    assert len(windowed.reports) == len(reqs)
    for r, req in zip(windowed.reports, reqs):
        assert not r.interrupted
        assert r.moved_mb == pytest.approx(req.dataset.total_mb)
    assert len(windowed.sessions) == len(strict.sessions)
    assert windowed.goodput_mbps == pytest.approx(
        strict.goodput_mbps, rel=0.10
    )
    assert windowed.makespan_s == pytest.approx(strict.makespan_s, rel=0.10)


def test_windowed_faulted_run_recovers_deterministically(dbs):
    faults = FaultSchedule.generate(
        23,
        start_s=START,
        horizon_s=90.0,
        n_flaps=0,
        n_drops=0,
        n_bursts=0,
        n_kills=4,
        n_tenants=8,
    )
    kw = dict(faults=faults, recovery=RecoveryConfig())
    _, _, a = _windowed_pair(dbs["xsede"], n=24, **kw)
    _, _, b = _windowed_pair(dbs["xsede"], n=24, **kw)
    assert canonical_trace(a) == canonical_trace(b)
    assert a.kills >= 1
    assert a.recoveries >= 1
    assert all(not r.interrupted for r in a.reports)  # recovery restored all


def test_windowed_engine_actually_windows(dbs):
    eng = ShardedFleetEngine(
        dbs["xsede"],
        EngineConfig(
            engine="sharded", n_shards=4, shard_window_s=120.0,
            max_concurrent=8, score_vs_single=False,
        ),
    )
    reqs = _scale_requests(64)
    fleet = eng.run(reqs)
    assert len(fleet.reports) == 64
    assert eng.windows_run >= 2  # the run really crossed window barriers


# ------------------------------------------------------------------ #
# windowed regime: exact fleets pinned by recorded traces
# ------------------------------------------------------------------ #
WINDOWED_GOLDEN_CASES = ("query", "routed", "refresh", "service")


def _golden_windowed_fleet(dbs, case):
    """A 24-request windowed fleet under the kill schedule of
    ``test_windowed_faulted_run_recovers_deterministically``, recovery on.

    ``case`` picks where admission finds its knowledge: frozen at a fixed
    cap (every admission queries the DB), frozen at the auto cap (first
    attempts take the fleet's routing pass, recoveries query), a
    ``RefreshConfig``, or a ``KnowledgeService``.  The last two fold
    finished sessions into the DB, so each gets a freshly fitted one.
    """
    faults = FaultSchedule.generate(
        23,
        start_s=START,
        horizon_s=90.0,
        n_flaps=0,
        n_drops=0,
        n_bursts=0,
        n_kills=4,
        n_tenants=8,
    )
    db = dbs["xsede"]
    kw = dict(max_concurrent=None if case == "routed" else 8)
    if case == "refresh":
        db = build_scenario_db("xsede")
        kw["refresh"] = RefreshConfig(every_completions=2, min_entries=4)
    elif case == "service":
        db = build_scenario_db("xsede")
        kw["knowledge"] = KnowledgeService(
            db, ServiceConfig(max_staleness_s=120.0, drift_threshold=0.1)
        )
    return run_fleet(
        db,
        _scale_requests(24),
        EngineConfig(
            engine="sharded", n_shards=4, shard_window_s=120.0,
            score_vs_single=False, faults=faults, recovery=RecoveryConfig(),
            **kw,
        ),
    )


@pytest.mark.parametrize("case", WINDOWED_GOLDEN_CASES)
def test_windowed_fleet_matches_recorded_trace(dbs, case):
    # the windowed regime's own fleet, not only its determinism: the
    # canonical trace equals the one recorded in tests/golden/
    trace = canonical_trace(_golden_windowed_fleet(dbs, case))
    want = json.loads((GOLDEN / f"windowed-{case}.json").read_text())
    assert json.loads(json.dumps(trace)) == want
    assert trace[1] >= 1  # a kill landed: recovery re-admission ran


# ------------------------------------------------------------------ #
# frontier / partition units
# ------------------------------------------------------------------ #
def test_frontier_pop_order_matches_single_heap():
    rng = np.random.default_rng(11)
    times = np.round(rng.uniform(0.0, 50.0, size=200), 1)  # force time ties
    slots = rng.permutation(200)
    frontier = ShardedEventFrontier(4, capacity=16)
    heap = VectorEventHeap(capacity=16)
    for t, s in zip(times, slots):
        frontier.push(float(t), int(s))
    heap.push_batch(times, slots)
    assert len(frontier) == len(heap) == 200
    merged = [frontier.pop() for _ in range(200)]
    single = [heap.pop() for _ in range(200)]
    assert merged == single  # (time, slot) tie rule survives the merge
    assert len(frontier) == 0


def test_frontier_push_batch_routes_by_slot_shard():
    frontier = ShardedEventFrontier(3)
    slots = np.arange(10, dtype=np.int64)
    frontier.push_batch(np.full(10, 5.0), slots)
    for s, heap in enumerate(frontier.shards):
        want = int(np.sum(slots % 3 == s))
        assert len(heap) == want
    # drain order under a uniform time is ascending slot id
    assert [frontier.pop()[1] for _ in range(10)] == list(range(10))


def test_frontier_empty_and_validation():
    frontier = ShardedEventFrontier(2)
    with pytest.raises(IndexError):
        frontier.peek()
    with pytest.raises(IndexError):
        frontier.pop()
    with pytest.raises(ValueError):
        ShardedEventFrontier(0)
    with pytest.raises(ValueError):
        frontier.push_batch(np.zeros(3), np.zeros(2, np.int64))
    frontier.push_batch(np.zeros(0), np.zeros(0, np.int64))  # no-op OK
    assert len(frontier) == 0


def test_slot_partition_is_cyclic_and_total():
    owners = slot_partition(10, 4)
    assert owners.tolist() == [0, 1, 2, 3, 0, 1, 2, 3, 0, 1]
    assert all(slot_shard(i, 4) == owners[i] for i in range(10))
    with pytest.raises(ValueError):
        slot_partition(10, 0)


def test_windowed_link_state_buffers_and_folds():
    shared = WindowedLinkState(IndexedSharedLink(TESTBEDS["xsede"]))
    shared.register(0, 100.0, 1000.0)
    shared.register(1, 50.0, 1000.0)
    shared.register(0, 200.0, 2000.0)  # re-registration overwrites in place
    # mid-window: nothing folded yet, aggregate still frozen at zero
    assert shared.snapshot(10.0, 2) == (0.0, 0)
    shared.begin_window(10.0)
    # folded: 200 + 50 visible to a third party...
    assert shared.snapshot(10.0, 2) == (250.0, 2)
    # ...and self-exclusion stays exact against the frozen aggregate
    assert shared.snapshot(10.0, 0) == (50.0, 1)
    assert shared.snapshot(10.0, 1) == (200.0, 1)
    # expiry at the next boundary drops both flows
    shared.begin_window(3000.0)
    assert shared.snapshot(3000.0, 2) == (0.0, 0)


# ------------------------------------------------------------------ #
# config plumbing
# ------------------------------------------------------------------ #
def test_engine_config_shard_validation():
    with pytest.raises(ValueError, match="n_shards"):
        EngineConfig(engine="sharded", n_shards=0)
    with pytest.raises(ValueError, match="shard_window_s"):
        EngineConfig(engine="sharded", shard_window_s=-1.0)
    with pytest.raises(ValueError, match="sharded"):
        EngineConfig(engine="vectorized", n_shards=2)
    with pytest.raises(ValueError, match="sharded"):
        EngineConfig(engine="threaded", shard_window_s=60.0)
    EngineConfig(engine="sharded", n_shards=2, shard_window_s=0.0)  # valid


def test_default_n_shards_is_host_device_count(dbs):
    # conftest pins XLA to 4 host devices, so the deferred default resolves
    # to 4 without the config naming a shard count.
    eng = ShardedFleetEngine(dbs["xsede"], EngineConfig(engine="sharded"))
    assert eng.n_shards == 4


def test_window_policy(dbs):
    auto = ShardedFleetEngine(
        dbs["xsede"], EngineConfig(engine="sharded", n_shards=4)
    )
    assert auto._window_s(8) is None  # parity scale stays strict
    assert auto._window_s(100_000) == DEFAULT_SHARD_WINDOW_S
    forced_strict = ShardedFleetEngine(
        dbs["xsede"],
        EngineConfig(engine="sharded", n_shards=4, shard_window_s=0.0),
    )
    assert forced_strict._window_s(100_000) is None
    single = ShardedFleetEngine(
        dbs["xsede"], EngineConfig(engine="sharded", n_shards=1)
    )
    assert single._window_s(100_000) is None  # nothing to reconcile
    forced_windowed = ShardedFleetEngine(
        dbs["xsede"],
        EngineConfig(engine="sharded", n_shards=4, shard_window_s=45.0),
    )
    assert forced_windowed._window_s(8) == 45.0


def test_accuracy_is_nan_only_when_scoring_disabled(dbs):
    _, shd = _both(
        dbs["xsede"], _requests(4), max_concurrent=2, score_vs_single=False
    )
    assert math.isnan(shd.accuracy_vs_single)
    _, scored = _both(dbs["xsede"], _requests(4), max_concurrent=2)
    assert not math.isnan(scored.accuracy_vs_single)
