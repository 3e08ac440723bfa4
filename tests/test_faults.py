"""Fault-injection netsim: schedule semantics, environment composition,
kill truncation (incl. the stale-flow-interval bugfix), and recovery
plumbing at the single-session level."""

import pytest

from repro.core import RecoveryConfig, TransferTuner, TunerConfig
from repro.core.online import AdaptiveSampler
from repro.netsim import (
    CapacityDrop,
    FaultSchedule,
    LinkFlap,
    LossBurst,
    SessionKilled,
    SharedLink,
    TenantEnvironment,
    TenantKill,
    TransferParams,
    XSEDE,
    generate_history,
    make_dataset,
    make_testbed,
)

PRM = TransferParams(4, 4, 4)


@pytest.fixture(scope="module")
def db():
    env = make_testbed("xsede", seed=3)
    hist = generate_history(env, days=4, transfers_per_day=120, seed=0)
    return TransferTuner(TunerConfig(seed=0)).fit(hist).db


# ------------------------------------------------------------------ #
# schedule semantics
# ------------------------------------------------------------------ #
def test_capacity_factors_compose_multiplicatively():
    fs = FaultSchedule((CapacityDrop(10.0, 20.0, factor=0.5),
                        LinkFlap(15.0, 10.0, residual=0.1)))
    assert fs.capacity_factor(5.0) == 1.0
    assert fs.capacity_factor(12.0) == 0.5
    assert fs.capacity_factor(16.0) == pytest.approx(0.05)  # both active
    assert fs.capacity_factor(27.0) == 0.5  # flap over, drop still on
    assert fs.capacity_factor(31.0) == 1.0


def test_link_at_perturbs_only_when_active():
    fs = FaultSchedule((LossBurst(10.0, 5.0, loss_sensitivity_mult=4.0,
                                  streams_to_saturate_mult=2.0,
                                  goodput_factor=0.5),))
    assert fs.link_at(XSEDE, 0.0) is XSEDE  # identical object off-fault
    lk = fs.link_at(XSEDE, 12.0)
    assert lk.loss_sensitivity == XSEDE.loss_sensitivity * 4.0
    assert lk.streams_to_saturate == XSEDE.streams_to_saturate * 2
    assert lk.bandwidth_mbps == XSEDE.bandwidth_mbps * 0.5


def test_next_change_walks_boundaries():
    fs = FaultSchedule((CapacityDrop(10.0, 20.0), LinkFlap(50.0, 5.0)))
    assert fs.next_change(0.0) == 10.0
    assert fs.next_change(10.0) == 30.0
    assert fs.next_change(30.0) == 50.0
    assert fs.next_change(55.0) == float("inf")


def test_kill_matching_and_ordering():
    fs = FaultSchedule((TenantKill(30.0, tenant_id=1), TenantKill(10.0),
                        TenantKill(20.0, tenant_id=1)))
    assert fs.next_kill(1, 0.0) == 10.0  # wildcard matches anyone
    assert fs.next_kill(1, 15.0) == 20.0
    assert fs.next_kill(2, 15.0) is None
    assert fs.next_kill(None, 0.0) == 10.0
    assert len(fs.kills()) == 3


def test_generate_is_deterministic_per_seed():
    a = FaultSchedule.generate(7, start_s=0.0, horizon_s=600.0, n_kills=2,
                               n_tenants=4)
    b = FaultSchedule.generate(7, start_s=0.0, horizon_s=600.0, n_kills=2,
                               n_tenants=4)
    c = FaultSchedule.generate(8, start_s=0.0, horizon_s=600.0, n_kills=2,
                               n_tenants=4)
    assert a.events == b.events
    assert a.events != c.events


# ------------------------------------------------------------------ #
# environment composition
# ------------------------------------------------------------------ #
def test_empty_schedule_matches_fault_free_path():
    from repro.netsim.environment import NOISE_BLOCK

    a = make_testbed("xsede", seed=1, constant_load=0.1)
    b = make_testbed("xsede", seed=1, constant_load=0.1)
    b.faults = FaultSchedule(())
    # chunk after chunk, across noise blocks and parameter changes
    for k in range(2 * NOISE_BLOCK + 8):
        prm = PRM if k % 7 < 4 else TransferParams(8, 2, 4)
        ra = a.transfer(prm, 800.0, 100.0, 50)
        rb = b.transfer(prm, 800.0, 100.0, 50)
        assert rb.effective_mbps == pytest.approx(ra.effective_mbps, rel=1e-12)
        assert rb.steady_mbps == pytest.approx(ra.steady_mbps, rel=1e-12)
        assert rb.elapsed_s == pytest.approx(ra.elapsed_s, rel=1e-12)
    assert a.n_noise_blocks == b.n_noise_blocks == 3


def test_faults_none_is_untouched_fast_path():
    a = make_testbed("xsede", seed=1, constant_load=0.1)
    assert a.faults is None
    r1 = a.transfer(PRM, 800.0, 100.0, 50)
    b = make_testbed("xsede", seed=1, constant_load=0.1)
    r2 = b.transfer(PRM, 800.0, 100.0, 50)
    assert r1 == r2  # bit-for-bit


def test_mid_chunk_drop_slows_the_chunk():
    free = make_testbed("xsede", seed=1, constant_load=0.1)
    r0 = free.transfer(PRM, 2000.0, 100.0, 50)
    faulted = make_testbed("xsede", seed=1, constant_load=0.1)
    faulted.faults = FaultSchedule((CapacityDrop(1.0, 1e6, factor=0.2),))
    r1 = faulted.transfer(PRM, 2000.0, 100.0, 50)
    assert r1.elapsed_s > r0.elapsed_s
    assert r1.steady_mbps < r0.steady_mbps


def test_flap_stalls_and_resumes():
    free = make_testbed("xsede", seed=1, constant_load=0.1)
    r0 = free.transfer(PRM, 2000.0, 100.0, 50)
    flapped = make_testbed("xsede", seed=1, constant_load=0.1)
    flapped.faults = FaultSchedule((LinkFlap(1.0, 30.0),))
    r1 = flapped.transfer(PRM, 2000.0, 100.0, 50)
    # the chunk crosses the flap: it pays (nearly) the whole dark window
    assert r1.elapsed_s > r0.elapsed_s + 20.0
    # but afterwards capacity restores, so it does finish
    assert r1.elapsed_s < r0.elapsed_s + 45.0


def test_kill_truncates_and_reports_progress():
    env = make_testbed("xsede", seed=1, constant_load=0.1)
    env.faults = FaultSchedule((TenantKill(1.5),))
    with pytest.raises(SessionKilled) as ei:
        env.transfer(PRM, 2000.0, 100.0, 50)
    assert ei.value.at_s == 1.5
    assert 0.0 < ei.value.moved_mb < 2000.0
    assert env.clock_s == 1.5  # clock stops at the kill instant


def test_kill_during_setup_moves_nothing():
    env = make_testbed("xsede", seed=1, constant_load=0.1)
    env.faults = FaultSchedule((TenantKill(0.01),))  # inside the setup ramp
    with pytest.raises(SessionKilled) as ei:
        env.transfer(PRM, 2000.0, 100.0, 50)
    assert ei.value.moved_mb == 0.0


def test_killed_tenant_leaves_no_stale_flow_interval():
    """Bugfix: a mid-chunk kill must truncate the tenant's flow interval at
    the kill instant — a full-chunk interval would impose phantom
    contention on the shared link long after the session died."""
    shared = SharedLink(XSEDE)
    base = make_testbed("xsede", seed=7, constant_load=0.1)
    env = TenantEnvironment(base.link, base.traffic, shared, 0, seed=7,
                            faults=FaultSchedule((TenantKill(2.0,
                                                             tenant_id=0),)))
    with pytest.raises(SessionKilled):
        env.transfer(PRM, 5000.0, 100.0, 50)
    # during the truncated chunk the flow was visible...
    assert shared.snapshot(1.0, exclude=99)[1] == 1
    # ...but not one instant past the kill
    assert shared.snapshot(2.0, exclude=99) == (0.0, 0)
    assert shared.snapshot(100.0, exclude=99) == (0.0, 0)


def test_kill_targets_only_matching_tenant():
    shared = SharedLink(XSEDE)
    base = make_testbed("xsede", seed=7, constant_load=0.1)
    env = TenantEnvironment(base.link, base.traffic, shared, 3, seed=7,
                            faults=FaultSchedule((TenantKill(1.0,
                                                             tenant_id=2),)))
    res = env.transfer(PRM, 500.0, 100.0, 50)  # other tenant's kill: no-op
    assert res.elapsed_s > 0


# ------------------------------------------------------------------ #
# dataset residuals + single-session recovery surface
# ------------------------------------------------------------------ #
def test_dataset_residual_is_byte_exact():
    ds = make_dataset("medium", 5)
    left = ds.residual(123.25)
    assert left.total_mb == pytest.approx(ds.total_mb - 123.25)
    assert left.avg_file_mb == ds.avg_file_mb
    assert left.n_files == ds.n_files
    # residual of more than remains clamps to zero
    assert ds.residual(ds.total_mb + 10).total_mb == 0.0


def test_sampler_returns_partial_report_on_kill(db):
    ds = make_dataset("medium", 7)
    env = make_testbed("xsede", seed=9, constant_load=0.15)
    env.clock_s = 4 * 3600.0
    env.faults = FaultSchedule((TenantKill(env.clock_s + 30.0),))
    rep = AdaptiveSampler(db, recovery=RecoveryConfig()).transfer(env, ds)
    assert rep.interrupted
    assert rep.checkpoint is not None
    assert 0.0 < rep.moved_mb < ds.total_mb
    assert rep.checkpoint.moved_mb == rep.moved_mb
    assert env.clock_s == pytest.approx(4 * 3600.0 + 30.0)


def test_sampler_fault_free_identical_with_recovery_config(db):
    ds = make_dataset("medium", 7)
    a = make_testbed("xsede", seed=9, constant_load=0.15)
    b = make_testbed("xsede", seed=9, constant_load=0.15)
    ra = AdaptiveSampler(db).transfer(a, ds)
    rb = AdaptiveSampler(db, recovery=RecoveryConfig()).transfer(b, ds)
    assert ra == rb  # detectors must never fire on a healthy link


def test_collapse_recovery_reprobes_on_capacity_drop(db):
    ds = make_dataset("medium", 7)
    env = make_testbed("xsede", seed=9, constant_load=0.15)
    env.clock_s = 4 * 3600.0
    env.faults = FaultSchedule((CapacityDrop(env.clock_s + 15.0, 600.0,
                                             factor=0.12),))
    rep = AdaptiveSampler(db, recovery=RecoveryConfig()).transfer(env, ds)
    assert not rep.interrupted
    assert rep.collapses >= 1  # the drop triggered an adaptive re-entry
    assert rep.moved_mb == pytest.approx(ds.total_mb)
