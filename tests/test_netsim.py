"""Network-simulator invariants the paper's assumptions rely on."""

import math

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.netsim import (
    make_testbed, make_dataset, ParamBounds, TransferParams, DiurnalTraffic,
    generate_history, CapacityDrop, FaultSchedule, IndexedSharedLink,
    LinkFlap, LossBurst, TenantEnvironment,
)
from repro.netsim.testbeds import _TRAFFIC, TESTBEDS, make_traffic

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


# ------------------------------------------------------------------ #
# the law's kept terms and the noise blocks change no value
# ------------------------------------------------------------------ #
def _plain_law(link, params, avg_file_mb, n_files, ext_load,
               contending_mbps=0.0, n_contending=0):
    """The throughput law with every term computed on every call: the
    reference ``Environment.mean_throughput``'s kept terms are held to."""
    cc, p, pp = params.cc, params.p, params.pp
    streams = cc * p
    window_cap = (link.tcp_buffer_mb * 8.0) / max(link.rtt_s, 1e-6)
    loss_cap = link.bandwidth_mbps / link.streams_to_saturate
    per_stream = min(window_cap, loss_cap)
    post_load = link.bandwidth_mbps * (1.0 - ext_load)
    avail = post_load - contending_mbps
    if n_contending > 0:
        avail = max(avail, post_load / (1.0 + n_contending))
    avail = max(avail, 0.05 * link.bandwidth_mbps)
    cpu_factor = min((cc / (cc + 1.5)) * 1.55, 1.30)
    if cc > link.cores:
        cpu_factor /= 1.0 + 0.25 * (cc - link.cores)
    agg = min(streams * per_stream * cpu_factor, avail)
    over = (streams * per_stream) / max(avail * link.congestion_knee, 1e-6)
    if over > 1.0:
        agg /= 1.0 + 0.12 * link.loss_sensitivity * (over - 1.0)
    rate = max(agg, 1e-3)
    xfer_time = (avg_file_mb * 8.0) / rate
    eff_pp = min(pp, max(n_files // max(cc, 1), 1))
    overhead = link.rtt_s / (eff_pp * max(1.0, 0.8 * cc))
    agg *= xfer_time / (xfer_time + overhead)
    return float(min(agg, link.disk_read_mbps, link.disk_write_mbps))


_DATASETS = [make_dataset(size, 11) for size in ("small", "medium", "large")]


@pytest.mark.parametrize("contenders", [(0.0, 0), (2_400.0, 3)],
                         ids=["alone", "contended"])
@pytest.mark.parametrize("load", [0.0, 0.15, 0.6, 0.95])
@pytest.mark.parametrize("testbed", ["xsede", "didclab"])
def test_kept_law_terms_equal_the_plain_law_on_the_lattice(testbed, load,
                                                           contenders):
    from repro.netsim.environment import LAW_TERMS_CAP

    env = make_testbed(testbed, seed=0)
    contending, n_active = contenders
    lattice = B.grid()
    for ds in _DATASETS:
        want = [_plain_law(env.link, prm, ds.avg_file_mb, ds.n_files, load,
                           contending, n_active) for prm in lattice]
        for _ in range(2):  # the first pass keeps the terms, the second reads
            got = [env.mean_throughput(prm, ds.avg_file_mb, ds.n_files, load,
                                       contending_mbps=contending,
                                       n_contending=n_active)
                   for prm in lattice]
            assert got == want
    # three datasets' lattices overflow the bound: the oldest keys went
    assert env.n_law_terms == len(_DATASETS) * len(lattice)
    assert len(env._law_memo) == LAW_TERMS_CAP


@pytest.mark.parametrize("fault", [
    CapacityDrop(0.0, 100.0, factor=0.3),
    LinkFlap(0.0, 100.0),
    LossBurst(0.0, 100.0),
], ids=["capacity-drop", "link-flap", "loss-burst"])
def test_an_overriding_link_gets_its_own_law(fault):
    env = make_testbed("xsede", seed=0)
    ds = _DATASETS[1]
    args = (TransferParams(8, 4, 4), ds.avg_file_mb, ds.n_files, 0.15)
    kept = env.mean_throughput(*args, contending_mbps=900.0, n_contending=2)
    # the spec the fault path hands the law inside the fault
    other = FaultSchedule((fault,)).link_at(env.link, 50.0)
    got = env.mean_throughput(*args, contending_mbps=900.0, n_contending=2,
                              link=other)
    assert got == _plain_law(other, *args, 900.0, 2)
    assert got != kept
    # the override kept nothing; the static link's value is as it was
    assert env.n_law_terms == 1 and len(env._law_memo) == 1
    assert env.mean_throughput(*args, contending_mbps=900.0,
                               n_contending=2) == kept


class _ScalarNoise(TenantEnvironment):
    """A tenant that draws its noise one scalar per chunk from its own
    generator: the reference the noise blocks are held to."""

    def __init__(self, *args, seed, **kwargs):
        super().__init__(*args, seed=seed, **kwargs)
        self._scalar_rng = np.random.default_rng(seed)

    def _noise(self):
        return float(self._scalar_rng.normal(0.0, self.noise_sigma))


def _tenant(cls, testbed, seed):
    link = TESTBEDS[testbed]
    return cls(link, make_traffic(testbed, seed=seed), IndexedSharedLink(link),
               0, seed=seed)


def _session(env, n=45):
    """Chunks and steady-state readings interleaved, with parameter changes:
    ``n`` noise draws in all."""
    out = []
    for k in range(n):
        prm = TransferParams(2 + k // 9, 4, 1 + k % 3 // 2 * 7)
        ds = _DATASETS[k % 3]
        if k % 4 == 3:
            out.append(env.measure_steady(prm, ds.avg_file_mb, ds.n_files))
        else:
            out.append(env.transfer(prm, 400.0, ds.avg_file_mb, ds.n_files,
                                    is_sample=k % 5 == 0))
    return out, env.clock_s, env.sample_count


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
@pytest.mark.parametrize("testbed", ["xsede", "didclab"])
def test_noise_blocks_give_the_scalar_draws(testbed, seed):
    from repro.netsim.environment import NOISE_BLOCK

    env = _tenant(TenantEnvironment, testbed, seed)
    assert _session(env) == _session(_tenant(_ScalarNoise, testbed, seed))
    assert env.n_noise_blocks == -(-45 // NOISE_BLOCK) >= 3
