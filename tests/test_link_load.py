"""The link's one external load (``netsim.DiurnalLinkLoad``) and the fleet
engines that share it.

The load is a pure function of simulated time: readings do not depend on
the order or number of readings before them, and its walk keeps the
stationary sd of an AR(1) walk.  Every engine builds one load per fleet
for requests with neither ``traffic`` nor ``constant_load``, the engines
agree bit for bit under it, and each chunk records the load it ran under.
"""

import math

import numpy as np
import pytest

import repro.core.fleet as fleet_mod
from repro.core import EngineConfig, FleetRequest, run_fleet
from repro.netsim import DiurnalLinkLoad, make_dataset, make_link_load
from repro.netsim.testbeds import _TRAFFIC
from repro.netsim.traffic import WALK_STEP_S
from repro.testing import build_scenario_db, canonical_trace

START = 9 * 3600.0
TIMES = [START + 37.3 * k for k in range(400)] + [0.0, 1.0, 86399.9, 2e5]


@pytest.mark.parametrize("testbed", ["didclab", "xsede"])
def test_load_is_a_pure_function_of_time(testbed):
    rng = np.random.default_rng(5)
    a = make_link_load(testbed, seed=11)
    want = [a.load_at(t) for t in TIMES]
    b = make_link_load(testbed, seed=11)
    # another instance, read in shuffled order, some times many times over
    order = rng.permutation(len(TIMES)).tolist()
    order += rng.integers(len(TIMES), size=300).tolist()
    got = {}
    for i in order:
        got.setdefault(i, b.load_at(TIMES[i]))
        assert b.load_at(TIMES[i]) == got[i]
    assert [got[i] for i in range(len(TIMES))] == want
    # the same again after the walk was extended far past these times
    assert [b.load_at(t) for t in TIMES] == want
    assert a == b and hash(a) == hash(b)
    assert [make_link_load(testbed, seed=12).load_at(t)
            for t in TIMES] != want


def test_load_is_piecewise_constant_in_the_walk():
    load = make_link_load("didclab", seed=3)
    step = WALK_STEP_S
    k = int(START // step)
    assert load.walk_at(k * step) == load.walk_at((k + 1) * step - 1e-6)
    assert load.walk_at(k * step) != load.walk_at((k + 1) * step)
    assert load.walk_at(-5.0) == load.walk_at(0.0) == 0.0
    # the diurnal term is continuous; the load is clipped to [0, 0.95]
    assert all(0.0 <= load.load_at(t) <= 0.95 for t in TIMES)


def test_walk_keeps_the_stationary_sd_of_an_ar1_walk():
    load = DiurnalLinkLoad(jitter=0.04, seed=9)
    n = 200_000
    walk = np.array([load.walk_at(k * WALK_STEP_S) for k in range(n)])
    want = 0.04 / math.sqrt(1.0 - 0.98**2)
    # past the first few correlation times of 50 steps
    assert walk[1000:].std() == pytest.approx(want, rel=0.05)
    assert abs(walk[1000:].mean()) < 0.2 * want


def test_link_load_takes_the_testbeds_diurnal_parameters():
    load = make_link_load("didclab", seed=4)
    for key, value in _TRAFFIC["didclab"].items():
        assert getattr(load, key) == value
    assert load.jitter == 0.04 and load.seed == 4


# --------------------------------------------------------------------- #
# the engines under the shared load
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def db():
    return build_scenario_db("didclab", days=2.0)


def _requests(n, *, constant_load=None):
    sizes = ("small", "medium", "large")
    return [FleetRequest(dataset=make_dataset(sizes[i % 3], 7 + i),
                         env_seed=99 + i, start_clock_s=START + 150.0 * i,
                         constant_load=constant_load)
            for i in range(n)]


ENGINES = {
    "threaded": {},
    "vectorized": {},
    "sharded-strict": {"n_shards": 2, "shard_window_s": 0.0},
}


def _run(db, reqs, engine):
    kw = ENGINES[engine]
    return run_fleet(db, reqs, EngineConfig(
        engine=engine.split("-")[0], testbed="didclab", max_concurrent=4,
        **kw))


@pytest.fixture(scope="module")
def reports(db):
    return {engine: _run(db, _requests(10), engine) for engine in ENGINES}


@pytest.mark.parametrize("engine", ["vectorized", "sharded-strict"])
def test_engines_agree_bit_for_bit_under_the_shared_load(reports, engine):
    oracle, got = reports["threaded"], reports[engine]
    assert got.reports == oracle.reports
    assert canonical_trace(got) == canonical_trace(oracle)
    assert got.accuracy_vs_single == oracle.accuracy_vs_single


@pytest.mark.parametrize("engine", list(ENGINES))
def test_every_chunk_records_the_links_load_at_its_start(reports, engine):
    load = make_link_load("didclab", seed=99)  # request 0's env_seed
    records = [r for s in reports[engine].sessions for r in s.report.samples]
    assert len(records) > 40
    assert all(r.ext_load == load.load_at(r.clock_s) for r in records)
    # the chunks of a session follow one another in simulated time
    for s in reports[engine].sessions:
        starts = [r.clock_s for r in s.report.samples]
        assert starts[0] == s.admit_s and starts == sorted(starts)


def test_a_fleet_builds_one_load(db, monkeypatch):
    made = []
    real = fleet_mod.make_link_load
    monkeypatch.setattr(fleet_mod, "make_link_load",
                        lambda *a, **k: made.append(k) or real(*a, **k))
    _run(db, _requests(6), "vectorized")
    assert made == [{"seed": 99}]
    # a constant load builds none, and every chunk records the constant
    rep = _run(db, _requests(6, constant_load=0.15), "vectorized")
    assert made == [{"seed": 99}]
    assert {r.ext_load for s in rep.sessions for r in s.report.samples} == {
        0.15}


def test_requests_with_their_own_load_keep_it():
    own = make_link_load("didclab", seed=1)
    reqs = [FleetRequest(dataset=make_dataset("small", 1), traffic=own),
            FleetRequest(dataset=make_dataset("small", 2), env_seed=5),
            FleetRequest(dataset=make_dataset("small", 3), constant_load=0.3)]
    got = fleet_mod.with_link_load(reqs, "didclab")
    assert got[0].traffic is own and got[2] is reqs[2]
    # the link's load is seeded from request 0, whatever its own traffic
    assert got[1].traffic == make_link_load("didclab", seed=0)
    assert fleet_mod.with_link_load(got, "didclab") is got
