"""Fleet scheduler: shared-link contention, determinism, admission,
re-probe storm damping, and N=1 equivalence with the single-tenant path."""

import pytest

from repro.core import (
    FleetConfig,
    FleetRequest,
    FleetScheduler,
    ReprobeLimiter,
    TransferTuner,
    TunerConfig,
)
from repro.netsim import (
    Environment,
    SharedLink,
    StepTraffic,
    TenantEnvironment,
    TransferParams,
    XSEDE,
    generate_history,
    make_dataset,
    make_link_load,
    make_testbed,
)

START = 4 * 3600.0  # off-peak morning


@pytest.fixture(scope="module")
def db():
    env = make_testbed("xsede", seed=3)
    hist = generate_history(env, days=4, transfers_per_day=120, seed=0)
    return TransferTuner(TunerConfig(seed=0)).fit(hist).db


def _single_tenant_report(db, ds, seed, constant_load=None):
    from repro.core.online import AdaptiveSampler

    if constant_load is None:
        # a lone tenant under the link's one load, which a fleet seeds from
        # its request 0's env_seed
        env = Environment(XSEDE, make_link_load("xsede", seed=seed), seed=seed)
    else:
        env = make_testbed("xsede", seed=seed, constant_load=constant_load)
    env.clock_s = START
    return AdaptiveSampler(db).transfer(env, ds), env.clock_s


def test_n1_fleet_bit_for_bit(db):
    ds = make_dataset("medium", 7)
    want, _ = _single_tenant_report(db, ds, seed=99)
    fleet = FleetScheduler(db).run(
        [FleetRequest(dataset=ds, env_seed=99, start_clock_s=START)]
    )
    assert len(fleet.reports) == 1
    assert fleet.reports[0] == want  # bit-for-bit, not approx
    assert fleet.samples_p50 == want.n_samples
    assert fleet.samples_p99 == want.n_samples


def test_two_tenants_sharing_link_each_at_most_single_rate(db):
    ds = make_dataset("large", 9)
    reqs = [
        FleetRequest(dataset=ds, env_seed=s, start_clock_s=START, constant_load=0.2)
        for s in (99, 101)
    ]
    fleet = FleetScheduler(db, config=FleetConfig(max_concurrent=2)).run(reqs)
    assert len(fleet.reports) == 2
    for rep, req in zip(fleet.reports, reqs):
        single, _ = _single_tenant_report(db, ds, seed=req.env_seed, constant_load=0.2)
        assert rep.steady_mbps <= single.steady_mbps * 1.001
    # fair-share division should actually bite, not just not-exceed
    singles = [
        _single_tenant_report(db, ds, seed=s, constant_load=0.2)[0] for s in (99, 101)
    ]
    assert sum(r.steady_mbps for r in fleet.reports) < 0.9 * sum(
        s.steady_mbps for s in singles
    )


def test_fleet_runs_are_deterministic(db):
    def go():
        reqs = [
            FleetRequest(
                dataset=make_dataset("medium", 30 + i),
                env_seed=200 + i,
                start_clock_s=START,
                constant_load=0.15,
            )
            for i in range(6)
        ]
        return FleetScheduler(db, config=FleetConfig(max_concurrent=6)).run(reqs)

    a, b = go(), go()
    assert [r.steady_mbps for r in a.reports] == [r.steady_mbps for r in b.reports]
    assert a.goodput_mbps == b.goodput_mbps
    assert (a.reprobe_grants, a.reprobe_denials) == (
        b.reprobe_grants,
        b.reprobe_denials,
    )


def test_auto_admission_cap_bounded(db):
    reqs = [
        FleetRequest(
            dataset=make_dataset("medium", 40 + i),
            env_seed=300 + i,
            start_clock_s=START,
            constant_load=0.15,
        )
        for i in range(8)
    ]
    sched = FleetScheduler(db)
    demands = sched.predict_demands(reqs)
    assert demands.shape == (8,)
    assert (demands > 0).all()
    fleet = sched.run(reqs)
    assert 1 <= fleet.admitted_concurrency <= 8
    assert len(fleet.reports) == 8
    assert fleet.goodput_mbps > 0


def test_reprobe_limiter_spacing_and_lone_tenant_bypass():
    lim = ReprobeLimiter(min_interval_s=10.0, n_active_fn=lambda t: 3)
    assert lim(100.0)  # first grant is free
    assert not lim(105.0)  # too soon
    assert lim(111.0)  # interval elapsed
    assert (lim.grants, lim.denials) == (2, 1)

    lone = ReprobeLimiter(min_interval_s=10.0, n_active_fn=lambda t: 1)
    assert all(lone(100.0 + i) for i in range(5))  # never throttled
    assert lone.denials == 0


def test_tenant_environment_alone_matches_plain_environment():
    base = make_testbed("xsede", seed=7)
    tenant = TenantEnvironment(
        base.link, make_testbed("xsede", seed=7).traffic, SharedLink(XSEDE), 0,
        seed=7,
    )
    prm = TransferParams(4, 4, 4)
    a = base.transfer(prm, 500.0, 100.0, 50)
    b = tenant.transfer(prm, 500.0, 100.0, 50)
    assert a == b
    assert base.clock_s == tenant.clock_s


def test_shared_link_snapshot_excludes_self_and_expired():
    link = SharedLink(XSEDE)
    link.register(0, 1000.0, end_s=50.0)
    link.register(1, 2000.0, end_s=100.0)
    assert link.snapshot(20.0, exclude=1) == (1000.0, 1)
    assert link.snapshot(20.0, exclude=2) == (3000.0, 2)
    assert link.snapshot(60.0, exclude=2) == (2000.0, 1)  # tenant 0 expired
    link.release(1)
    assert link.snapshot(60.0, exclude=2) == (0.0, 0)


def test_step_traffic_schedule():
    tr = StepTraffic([(10.0, 0.5), (20.0, 0.1)], initial=0.0)
    assert tr.load_at(0.0) == 0.0
    assert tr.load_at(10.0) == 0.5
    assert tr.load_at(19.9) == 0.5
    assert tr.load_at(25.0) == pytest.approx(0.1)
    assert tr.is_peak(15.0) and not tr.is_peak(25.0)


def test_fleet_clock_ignores_future_admissions():
    from repro.core.fleet import _FleetClock

    clock = _FleetClock()
    clock.admit(0, 100.0)
    clock.admit(1, 5000.0)  # staggered: starts far in the future
    assert clock.n_active_at(200.0) == 1  # tenant 0 is genuinely alone
    assert clock.n_active_at(5000.0) == 2
    clock.finish(0)
    assert clock.n_active_at(200.0) == 0  # 0 retired at clock 100, 1 not begun
    assert clock.n_active_at(5000.0) == 1


def test_staggered_starts_respected(db):
    ds = make_dataset("small", 11)
    reqs = [
        FleetRequest(dataset=ds, env_seed=400, start_clock_s=START),
        FleetRequest(dataset=ds, env_seed=401, start_clock_s=START + 3600.0),
    ]
    fleet = FleetScheduler(db, config=FleetConfig(max_concurrent=2)).run(reqs)
    assert len(fleet.reports) == 2
    assert fleet.makespan_s >= 3600.0  # second tenant cannot start early


def test_fleet_goodput_rollup_consistent(db):
    reqs = [
        FleetRequest(
            dataset=make_dataset("medium", 60 + i),
            env_seed=600 + i,
            start_clock_s=START,
            constant_load=0.15,
        )
        for i in range(4)
    ]
    fleet = FleetScheduler(db, config=FleetConfig(max_concurrent=4)).run(reqs)
    total_mb = sum(r.dataset.total_mb for r in reqs)
    assert fleet.goodput_mbps == pytest.approx(total_mb * 8.0 / fleet.makespan_s)
    assert 0.0 < fleet.accuracy_vs_single <= 100.0
