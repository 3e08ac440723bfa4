"""The program's spans and counters (``repro.obs``) on the fleet path.

Each test records a CPU profiler trace of a small fleet and reads the
``repro.*`` host events back with ``jax.profiler.ProfileData``: the
``repro.fleet.score`` span nests in ``repro.fleet.run`` under one fleet
number, and the counters attached to the run span agree with the engine's
own counts.  The timers never feed the simulation: the report is
bit-identical with a profiler session open and with none, and a run with
tracing off reads no clock at all.
"""

import dataclasses
import glob

import jax
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.core import EngineConfig, FleetRequest, RecoveryConfig, run_fleet
from repro.core.engine import VectorizedFleetEngine
from repro.netsim import FaultSchedule, make_dataset
from repro.testing import build_scenario_db, canonical_trace

START = 4 * 3600.0
N = 64


@pytest.fixture(scope="module")
def db():
    return build_scenario_db("xsede")


def _requests(n=N):
    sizes = ("small", "medium", "large")
    return [
        FleetRequest(
            dataset=make_dataset(sizes[i % 3], 7 + i),
            env_seed=99 + i,
            start_clock_s=START,
        )
        for i in range(n)
    ]


def _config(**kw):
    return EngineConfig(engine="vectorized", score_vs_single=False, **kw)


def _traced(tmp_path, fn):
    """``fn()``'s result and the ``repro.*`` events of its CPU trace, as
    ``(name, start_ns, end_ns, stats)`` in start order."""
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [
        (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines
        for e in line.events
        if e.name.startswith(obs.PREFIX)
    ]
    return out, sorted(events, key=lambda e: e[1])


def _one(events, name):
    (event,) = [e for e in events if e[0] == name]
    return event


def _faulted():
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
    return _config(max_concurrent=4, faults=faults, recovery=RecoveryConfig())


@pytest.mark.parametrize("engine", ["vectorized", "threaded", "sharded"])
def test_score_span_nests_in_the_run_span_of_the_same_fleet(
    db, tmp_path, engine
):
    cfg = EngineConfig(engine=engine, score_vs_single=False)
    _, events = _traced(tmp_path, lambda: run_fleet(db, _requests(8), cfg))
    _, r0, r1, run = _one(events, "repro.fleet.run")
    _, s0, s1, score = _one(events, "repro.fleet.score")
    assert r0 <= s0 < s1 <= r1
    assert score["fleet"] == run["fleet"]
    assert run["requests"] == 8 and run["engine"] == engine
    assert score["clusters"] >= 1


def test_each_fleet_gets_the_next_number(db, tmp_path):
    def two():
        run_fleet(db, _requests(4), _config())
        run_fleet(db, _requests(4), _config())

    _, events = _traced(tmp_path, two)
    runs = [e[3]["fleet"] for e in events if e[0] == "repro.fleet.run"]
    scores = [e[3]["fleet"] for e in events if e[0] == "repro.fleet.score"]
    assert len(runs) == 2 and runs[1] == runs[0] + 1
    assert scores == runs


def test_events_counter_is_the_engines_event_count(db, tmp_path):
    reqs = _requests()
    _, events = _traced(tmp_path, lambda: run_fleet(db, reqs, _config()))
    engine = VectorizedFleetEngine(db, _config())
    engine.run(reqs)
    assert _one(events, "repro.fleet.run")[3]["events"] == (
        engine.events_processed
    )
    assert engine.counters is None  # no profiler session: nothing kept


def test_engine_counters_count_this_run_only(db, tmp_path):
    engine = VectorizedFleetEngine(db, _config())
    engine.run(_requests(8))
    before = engine.events_processed
    _traced(tmp_path, lambda: engine.run(_requests(8)))
    assert engine.counters["events"] == engine.events_processed - before
    assert engine.counters["admissions"] == 8
    # frozen knowledge and a computed cap: every admission took the
    # fleet's routing pass
    assert engine.counters["prerouted"] == 8


def test_admissions_count_requests_and_recoveries(db, tmp_path):
    report, events = _traced(
        tmp_path, lambda: run_fleet(db, _requests(8), _faulted())
    )
    assert report.recoveries >= 1  # the faults bit
    run = _one(events, "repro.fleet.run")[3]
    assert run["admissions"] == 8 + report.recoveries
    assert run["prerouted"] == 0  # a fixed cap scores no demands


def test_session_steps_hold_the_simulated_network(db, tmp_path):
    _, events = _traced(tmp_path, lambda: run_fleet(db, _requests(), _config()))
    run = _one(events, "repro.fleet.run")[3]
    assert run["step_ns"] >= run["netsim_ns"] > 0
    assert run["admit_ns"] > 0
    _, r0, r1, _ = _one(events, "repro.fleet.run")
    assert run["step_ns"] + run["admit_ns"] <= r1 - r0


@pytest.mark.parametrize("load", [0.15, None], ids=["constant", "diurnal"])
def test_load_readings_sit_in_the_network_and_grants_are_the_runs(
        db, tmp_path, load):
    reqs = [dataclasses.replace(r, constant_load=load) for r in _requests()]
    report, events = _traced(tmp_path, lambda: run_fleet(db, reqs, _config()))
    run = _one(events, "repro.fleet.run")[3]
    if load is None:
        assert run["netsim_ns"] >= run["load_ns"] > 0
    else:  # a constant load is not timed
        assert run["load_ns"] == 0 and run["netsim_ns"] > 0
    assert run["reprobe_grants"] == report.reprobe_grants


@pytest.mark.parametrize("load", [0.15, None], ids=["constant", "diurnal"])
def test_const_load_counts_admissions_on_constant_traffic(db, tmp_path, load):
    reqs = [dataclasses.replace(r, constant_load=load) for r in _requests(8)]
    engine = VectorizedFleetEngine(db, _config())
    engine.run(reqs)
    assert engine.counters is None  # no profiler session: no counters
    _, events = _traced(tmp_path, lambda: run_fleet(db, reqs, _config()))
    run = _one(events, "repro.fleet.run")[3]
    assert run["admissions"] == 8
    assert run["const_load"] == (8 if load is not None else 0)


@pytest.mark.parametrize("load", [0.15, None], ids=["constant", "diurnal"])
def test_law_terms_and_noise_blocks_count_the_networks_work(db, tmp_path, load):
    reqs = [dataclasses.replace(r, constant_load=load) for r in _requests()]
    report, events = _traced(tmp_path, lambda: run_fleet(db, reqs, _config()))
    run = _one(events, "repro.fleet.run")[3]
    chunks = sum(len(s.report.samples) for s in report.sessions)
    # every tenant draws its first block; a session's bulk chunks reuse
    # the terms of its parameter choice
    assert run["noise_blocks"] >= run["admissions"] == N
    assert 0 < run["law_terms"] < chunks


def test_the_strict_sharded_regime_carries_the_counters(db, tmp_path):
    cfg = EngineConfig(engine="sharded", n_shards=2, score_vs_single=False)
    _, events = _traced(tmp_path, lambda: run_fleet(db, _requests(8), cfg))
    run = _one(events, "repro.fleet.run")[3]
    assert run["admissions"] == 8 and run["events"] > 0


@pytest.mark.parametrize("faulted", [False, True], ids=["plain", "faulted"])
def test_report_is_bit_identical_with_tracing_on_and_off(db, tmp_path, faulted):
    reqs = _requests(8) if faulted else _requests()
    cfg = _faulted() if faulted else _config()
    off = run_fleet(db, reqs, cfg)
    on, _ = _traced(tmp_path, lambda: run_fleet(db, reqs, cfg))
    # repr spells every float exactly, and reads nan alike on both sides
    assert repr(on) == repr(off)
    assert canonical_trace(on) == canonical_trace(off)


def test_tracing_off_reads_no_clock(db, monkeypatch):
    def clock():
        raise AssertionError("clock read with tracing off")

    monkeypatch.setattr(obs, "now_ns", clock)
    assert not obs.active()
    run_fleet(db, _requests(8), _faulted())
