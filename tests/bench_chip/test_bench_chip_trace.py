"""The benchmark's yardstick: trace reduction, work counts, window arithmetic.

The traces under ``data/`` were recorded on a TPU v5e by the harness's own
traced runs (``--trace 1``) of the discovery and fleet cells, with short
windows so that they stay small.
"""
from __future__ import annotations

import gzip
import pathlib
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import clock, roofline, trace  # noqa: E402


@pytest.fixture(scope="module", params=["discovery", "fleet"])
def recorded(request, tmp_path_factory):
    xp = tmp_path_factory.mktemp("xplane") / "t.xplane.pb"
    xp.write_bytes(gzip.decompress(
        (DATA / f"{request.param}.xplane.pb.gz").read_bytes()))
    return request.param, trace.load_events(str(xp))


def _busy_by_sweep(intervals, w0, w1) -> float:
    """Covered length of ``(start, duration)`` intervals inside [w0, w1],
    by a sweep over sorted edges with an open-interval counter."""
    edges = sorted([(max(s, w0), 1) for s, d in intervals if s + d > w0]
                   + [(min(s + d, w1), -1) for s, d in intervals
                      if s + d > w0])
    covered, depth, last = 0.0, 0, None
    for x, step in edges:
        if depth > 0 and last is not None:
            covered += max(x - last, 0.0)
        depth += step
        last = x
    return covered


def test_recorded_trace_holds_one_device_and_the_window_span(recorded):
    _, ev = recorded
    assert len(ev.ops) == 1 and len(ev.modules) == 1
    assert len(ev.ops[0]) > 0
    assert [n for n, _, _ in ev.spans].count("window") == 1


def test_busy_union_and_idle_share_match_a_plain_sweep(recorded):
    _, ev = recorded
    s = trace.summarize(ev)
    (w0, w1), = [(a, a + d) for n, a, d in ev.spans if n == "window"]
    busy = _busy_by_sweep(ev.ops[0], w0, w1)
    assert s.busy_s == pytest.approx(busy * 1e-9, rel=1e-12)
    assert s.window_s == pytest.approx((w1 - w0) * 1e-9, rel=1e-12)
    assert s.idle_share == pytest.approx(1 - busy / (w1 - w0), rel=1e-12)
    assert 0.0 < s.busy_s < s.window_s


def test_per_program_time_and_top_programs(recorded):
    cell, ev = recorded
    s = trace.summarize(ev)
    (w0, w1), = [(a, a + d) for n, a, d in ev.spans if n == "window"]
    want: dict[str, float] = {}
    for name, a, d in ev.modules[0]:
        inside = min(a + d, w1) - max(a, w0)
        if inside > 0:
            want[name] = want.get(name, 0.0) + inside * 1e-9
    assert s.program_s == pytest.approx(want)
    assert [n for n, _ in s.top_ops] == sorted(want, key=lambda k: -want[k]
                                               )[:10]
    if cell == "discovery":
        # the two sweeps of fit_clusters are the programs that take most time
        assert {n for n, _ in s.top_ops[:2]} == {"refine_and_stats",
                                                 "minibatch_sweep"}


def test_idle_gaps_are_the_longest_and_named_by_host_spans(recorded):
    _, ev = recorded
    s = trace.summarize(ev)
    gaps = [g for _, g in s.idle_gaps]
    assert gaps == sorted(gaps, reverse=True) and len(gaps) <= 10
    assert sum(gaps) <= s.window_s - s.busy_s + 1e-9
    names = {n for n, _, _ in ev.spans} | {"untraced host"}
    assert {n for n, _ in s.idle_gaps} <= names


def test_hand_made_events():
    ev = trace.Events(
        ops=[[(0.0, 10.0), (5.0, 10.0), (30.0, 5.0)]],
        modules=[[("p", 0.0, 15.0), ("q", 30.0, 5.0)]],
        spans=[("window", 0.0, 50.0), ("host.work", 16.0, 10.0)])
    s = trace.summarize(ev)
    assert s.busy_s == pytest.approx(20e-9)
    assert s.window_s == pytest.approx(50e-9)
    assert s.idle_share == pytest.approx(0.6)
    assert s.program_s == pytest.approx({"p": 15e-9, "q": 5e-9})
    assert s.top_ops[0][0] == "p"
    # gaps 15..30 (mid 22.5, inside host.work) and 35..50 (window only)
    assert s.idle_gaps == [("window", pytest.approx(15e-9)),
                           ("host.work", pytest.approx(15e-9))] or \
        s.idle_gaps == [("host.work", pytest.approx(15e-9)),
                        ("window", pytest.approx(15e-9))]


def test_program_names():
    assert trace.program_name("jit_refine_and_stats(9835959209798405120)") \
        == "refine_and_stats"
    assert trace.program_name("jit__take(6435383128850200195)") == "_take"


# --------------------------------------------------------------------- #
# operations and bytes of the discovery sweeps
# --------------------------------------------------------------------- #
def test_sweep_work_against_a_hand_count():
    # n=10 rows, d=2, orders m=2 and m=3, 1 mini-batch of 4, no refinement:
    # per point 3*2*2+2 + 3*2*3+2 = 34 flops; 4 + 10 points -> 476 flops.
    # bytes: points 4*2*(4+10)=112, labels 4*10*2=80, centroids
    # 2*(4*2*3*2)*(1+1)=192 -> 384.
    flops, nbytes = roofline.discovery_sweep_work(10, 2, [2, 3], 4, 1, 0)
    assert flops == 476.0
    assert nbytes == 384.0


def test_discovery_sweeps_are_bytes_bound_on_v5e():
    flops, nbytes = roofline.discovery_sweep_work(
        1_000_000, 4, list(range(4, 13)), 2048, 80, 5)
    t, bound = roofline.least_time_s(flops, nbytes,
                                     roofline.peaks("TPU v5 lite"))
    assert bound == "bytes"
    assert t == pytest.approx(nbytes / 819e9)
    # about 133 MB of traffic: 0.16 ms at 819 GB/s
    assert 1.2e8 < nbytes < 1.5e8


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")


# --------------------------------------------------------------------- #
# window arithmetic: a rate is all the work over all the window's time
# --------------------------------------------------------------------- #
def test_fleet_rate_counts_every_fleet_over_the_whole_window(monkeypatch):
    import repro.core as core
    from benchmarks.chip.drivers import fleet

    pauses = iter([0.05, 0.15, 0.05, 0.15, 0.05, 0.15, 0.05, 0.15])
    monkeypatch.setattr(core, "run_fleet",
                        lambda db, reqs, cfg: time.sleep(next(pauses)))
    monkeypatch.setattr(fleet, "requests", lambda c, t, s: [])
    monkeypatch.setattr(fleet, "plain", lambda reqs, report: None)
    state = fleet.State({}, {"sessions": 100}, None, None,
                        np.random.default_rng(0))
    t0 = time.perf_counter()
    res = fleet.window(state, 0.3, clock.Spans())
    wall = time.perf_counter() - t0
    n = res["units"]
    assert n >= 2 and res["attempted"] == 100 * n
    rate = res["e2e"]["fleet_sessions_per_s"]
    # the rate is sessions over the time to the last fleet's end, which is
    # the whole wall time of the window here, not a mean of fleet rates
    assert rate == pytest.approx(100 * n / wall, rel=0.05)
    per_fleet = np.mean([100 / p for p in [0.05, 0.15] * 4][:n])
    assert rate < per_fleet


def test_discovery_time_is_window_time_over_discoveries(monkeypatch):
    from repro.core import clustering

    from benchmarks.chip.drivers import discovery

    pauses = iter([0.02, 0.1] * 20)

    def fake(X, **kw):
        time.sleep(next(pauses))
        return object()

    monkeypatch.setattr(clustering, "fit_clusters", fake)
    state = discovery.State({}, {"m_range": [4, 6]}, 0, [None, None],
                            np.random.default_rng(0), [0.5])
    t0 = time.perf_counter()
    res = discovery.window(state, 0.35, clock.Spans())
    wall = time.perf_counter() - t0
    n = res["units"]
    assert n >= 3
    assert res["e2e"]["discovery_s"] == pytest.approx(wall / n, rel=0.05)
