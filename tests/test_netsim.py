"""Network-simulator invariants the paper's assumptions rely on."""

import math

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.netsim import (
    make_testbed, make_dataset, ParamBounds, TransferParams, DiurnalTraffic,
    generate_history,
)
from repro.netsim.testbeds import _TRAFFIC, make_traffic

B = ParamBounds()


@given(st.integers(1, 16), st.integers(1, 16), st.integers(1, 16),
       st.floats(0.0, 0.9))
@settings(max_examples=60, deadline=None)
def test_throughput_positive_and_bounded(cc, p, pp, load):
    env = make_testbed("xsede", seed=0)
    ds = make_dataset("medium", 0)
    th = env.mean_throughput(TransferParams(cc, p, pp), ds.avg_file_mb,
                             ds.n_files, load)
    assert 0.0 < th <= env.link.bandwidth_mbps
    assert th <= env.link.disk_read_mbps


@given(st.integers(1, 16), st.integers(1, 16), st.integers(1, 16))
@settings(max_examples=30, deadline=None)
def test_more_load_never_helps(cc, p, pp):
    env = make_testbed("xsede", seed=0)
    ds = make_dataset("large", 1)
    prm = TransferParams(cc, p, pp)
    th_light = env.mean_throughput(prm, ds.avg_file_mb, ds.n_files, 0.05)
    th_heavy = env.mean_throughput(prm, ds.avg_file_mb, ds.n_files, 0.6)
    assert th_heavy <= th_light + 1e-9


def test_pipelining_helps_small_files_on_wan():
    env = make_testbed("xsede", seed=0)
    th1 = env.mean_throughput(TransferParams(4, 2, 1), 2.0, 2000, 0.1)
    th16 = env.mean_throughput(TransferParams(4, 2, 16), 2.0, 2000, 0.1)
    assert th16 > th1 * 1.5


def test_pipelining_irrelevant_for_large_files():
    env = make_testbed("xsede", seed=0)
    th1 = env.mean_throughput(TransferParams(4, 2, 1), 8000.0, 10, 0.1)
    th16 = env.mean_throughput(TransferParams(4, 2, 16), 8000.0, 10, 0.1)
    assert abs(th16 - th1) / th1 < 0.05


def test_paper_cc_vs_p_example():
    """Sec 4.1: cc=8,p=2 beats cc=4,p=4 (same 16 streams, more processes)."""
    env = make_testbed("xsede", seed=0)
    th_8_2 = env.mean_throughput(TransferParams(8, 2, 4), 150.0, 200, 0.1)
    th_4_4 = env.mean_throughput(TransferParams(4, 4, 4), 150.0, 200, 0.1)
    assert th_8_2 > th_4_4


def test_oversubscription_hurts():
    env = make_testbed("didclab-xsede", seed=0)
    ds = make_dataset("large", 2)
    th_sane = env.mean_throughput(TransferParams(4, 3, 2), ds.avg_file_mb,
                                  ds.n_files, 0.1)
    th_crazy = env.mean_throughput(TransferParams(16, 16, 2), ds.avg_file_mb,
                                   ds.n_files, 0.1)
    assert th_crazy < th_sane


def test_didclab_disk_bound():
    """Sec 4.2: DIDCLAB throughput is bounded by the 90 MB/s disks."""
    env = make_testbed("didclab", seed=0)
    _, opt_th = env.optimal(B, 150.0, 100, 0.05)
    assert opt_th <= 720.0 + 1e-6
    assert opt_th > 600.0


def test_diurnal_traffic_peak_structure():
    tr = DiurnalTraffic(base_load=0.1, peak_load=0.5, peak_hour=13.0,
                        peak_width_h=2.0, jitter=0.0)
    noon = tr.load_at(13 * 3600.0)
    night = tr.load_at(3 * 3600.0)
    assert noon > night + 0.3
    assert tr.is_peak(13 * 3600.0)
    assert not tr.is_peak(3 * 3600.0)


class _DrawEveryReading:
    """``DiurnalTraffic.load_at`` as first written: a generator built up
    front and one draw per reading, even a draw of zero variance."""

    def __init__(self, tr):
        self.tr = tr
        self.rng = np.random.default_rng(tr.seed)
        self.walk = 0.0

    def load_at(self, t_s):
        tr = self.tr
        hour = (t_s % (24 * 3600.0)) / 3600.0
        d = min(abs(hour - tr.peak_hour), 24.0 - abs(hour - tr.peak_hour))
        diurnal = tr.peak_load * math.exp(-0.5 * (d / tr.peak_width_h) ** 2)
        self.walk = 0.98 * self.walk + self.rng.normal(0.0, tr.jitter)
        load = tr.base_load + diurnal + self.walk
        return float(min(max(load, 0.0), 0.95))


_CASES = [pytest.param(lambda load=load: DiurnalTraffic.constant(load),
                       id=f"constant-{load}")
          for load in (0.0, 0.15, 0.5, 0.95, 1.2)] + [
    pytest.param(lambda name=name, seed=seed: make_traffic(name, seed=seed),
                 id=f"{name}-seed{seed}")
    for name in _TRAFFIC for seed in (0, 7, 2**31 + 5)]


@pytest.mark.parametrize("make", _CASES)
def test_traffic_readings_are_bit_identical_to_a_draw_per_reading(make):
    tr, ref = make(), _DrawEveryReading(make())
    # a little over a day in uneven steps, through the peak and past midnight
    times = [3 * 3600.0 + 97.3 * k for k in range(1000)]
    assert [tr.load_at(t) for t in times] == [ref.load_at(t) for t in times]


@pytest.fixture
def made(monkeypatch):
    """The arguments of every ``np.random.default_rng`` call from here on."""
    calls = []
    real = np.random.default_rng

    def counted(*args, **kwargs):
        calls.append(args)
        return real(*args, **kwargs)

    monkeypatch.setattr(np.random, "default_rng", counted)
    return calls


def test_constant_traffic_builds_no_generator(made):
    env = make_testbed("xsede", seed=3, constant_load=0.15)
    assert env.traffic.is_constant
    loads = {env.traffic.load_at(60.0 * k) for k in range(100)}
    assert loads == {0.15}
    assert made == [(3,)]  # the environment's own noise generator alone


def test_jittered_traffic_builds_its_generator_on_the_first_draw(made):
    tr = make_traffic("xsede", seed=4)
    assert not tr.is_constant and made == []
    tr.load_at(0.0)
    assert made == [(4 + 17,)]
    for k in range(100):
        tr.load_at(60.0 * k)
    assert made == [(4 + 17,)]


def test_transfer_session_reuse_skips_setup():
    env = make_testbed("xsede", seed=1)
    prm = TransferParams(4, 4, 4)
    r1 = env.transfer(prm, 500.0, 100.0, 50)
    r2 = env.transfer(prm, 500.0, 100.0, 50)
    # second chunk with identical params re-uses sessions -> faster
    assert r2.effective_mbps > r1.effective_mbps * 0.99
    r3 = env.transfer(TransferParams(8, 2, 4), 500.0, 100.0, 50)
    assert r3.effective_mbps < r3.steady_mbps  # setup charged on change


def test_history_generation_schema():
    env = make_testbed("didclab", seed=5)
    hist = generate_history(env, days=1.0, transfers_per_day=50, seed=7)
    assert len(hist) == 50
    assert all(h.timestamp_s <= 24 * 3600 for h in hist)
    assert all(h.throughput_mbps >= 0 for h in hist)
    assert all(1 <= h.cc <= 16 and 1 <= h.p <= 16 and 1 <= h.pp <= 16
               for h in hist)
    # sorted by time
    ts = [h.timestamp_s for h in hist]
    assert ts == sorted(ts)


def test_optimal_grid_search_consistency():
    env = make_testbed("xsede", seed=0)
    ds = make_dataset("medium", 3)
    prm, th = env.optimal(B, ds.avg_file_mb, ds.n_files, 0.2)
    # no grid point beats the reported optimum
    for cand in [TransferParams(4, 4, 4), TransferParams(8, 2, 16),
                 TransferParams(16, 16, 16), TransferParams(1, 1, 1)]:
        assert env.mean_throughput(cand, ds.avg_file_mb, ds.n_files, 0.2) <= th + 1e-9


def test_regime_shift_traffic_deterministic_step():
    from repro.netsim import RegimeShiftTraffic

    tr = RegimeShiftTraffic(shift_s=1000.0, before=0.1, after=0.6)
    assert tr.load_at(0.0) == 0.1
    assert tr.load_at(999.9) == 0.1
    assert tr.load_at(1000.0) == 0.6
    assert tr.load_at(5e6) == 0.6
    assert not tr.is_peak(500.0) and tr.is_peak(1500.0)
    # pure function of t: replays identically, hashable for benchmark caches
    assert tr.load_at(777.0) == tr.load_at(777.0)
    assert hash(tr) == hash(RegimeShiftTraffic(shift_s=1000.0, before=0.1,
                                               after=0.6))
    rippled = RegimeShiftTraffic(shift_s=1000.0, before=0.05, after=0.9,
                                 ripple=0.1)
    for t in (0.0, 250.0, 900.0, 1100.0, 3600.0):
        assert 0.0 <= rippled.load_at(t) <= 0.95
