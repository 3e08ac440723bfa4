"""The reference's ``load_gap`` (``reference.fleet_load``) and the
``fleet_load`` driver of cell ``fleet.didclab-poisson``.

The reference recomputes the link's one external load from the
configuration's ``load`` block with its own float64 code; here it is held
against the program's ``DiurnalLinkLoad``, against a small fleet's
recorded chunks, and against the two ways of breaking the shared load the
driver's controls plant: one chunk's load off by 0.01, and a load of its
own per tenant.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from cellcheck import CHIP  # noqa: E402

from benchmarks.chip.drivers import fleet_load  # noqa: E402
from benchmarks.chip.reference import fleet_load as ref  # noqa: E402

CONFIG = json.loads((CHIP / "configs" / "didclab-lan.json").read_text())
TRAFFIC = json.loads((CHIP / "traffic" / "poisson-2k.json").read_text())
LOAD = CONFIG["load"]
LIMIT = TRAFFIC["limits"]["load_gap"]
START = TRAFFIC["start_clock_s"]


def test_the_load_block_states_the_programs_load():
    from repro.netsim import make_link_load
    from repro.netsim.traffic import LOAD_STREAM, WALK_AR, WALK_STEP_S

    load = make_link_load(CONFIG["testbed"], seed=0)
    for key in ("base_load", "peak_load", "peak_hour", "peak_width_h",
                "jitter"):
        assert LOAD[key] == getattr(load, key), key
    assert (LOAD["ar"], LOAD["seed_stream"], LOAD["walk_step_s"]) == (
        WALK_AR, LOAD_STREAM, WALK_STEP_S)


@pytest.mark.parametrize("seed", [0, 7, 2**31 - 1])
def test_reference_load_is_the_programs_within_rounding(seed):
    from repro.netsim import make_link_load

    load = make_link_load(CONFIG["testbed"], seed=seed)
    t = START + np.concatenate([np.linspace(0.0, 86400.0, 3001),
                                [-START, -1.0, 1e5, 3.3e5]])
    got = ref.link_load(LOAD, seed, t)
    want = np.array([load.load_at(float(x)) for x in t])
    assert np.max(np.abs(got - want)) <= 4e-16
    # the walk is the same sequence of doubles
    k = int(np.floor(t.max() / LOAD["walk_step_s"])) + 1
    walk = ref.walk(LOAD, seed, k)
    assert walk.tolist() == [load.walk_at(i * LOAD["walk_step_s"])
                             for i in range(k)]


@pytest.fixture(scope="module")
def fleet():
    """A fleet of 12 requests under the diurnal load, and its plain
    answers as the driver hands them to the reference."""
    from repro.core import EngineConfig, FleetRequest, run_fleet
    from repro.netsim import make_dataset
    from repro.testing import build_scenario_db

    db = build_scenario_db(CONFIG["testbed"], days=1.0)
    reqs = [FleetRequest(dataset=make_dataset(c, 5 + i), env_seed=4000 + i,
                         start_clock_s=START + 300.0 * i)
            for i, c in enumerate(["small", "medium", "large"] * 4)]
    engine = EngineConfig(engine="vectorized", testbed=CONFIG["testbed"],
                          score_vs_single=False)
    return db, reqs, engine, fleet_load.plain(reqs, run_fleet(db, reqs, engine))


def _gap(answers) -> float:
    return ref.load_gap(answers[2]["chunks"], LOAD, answers[2]["load_seed"])


def test_program_reads_within_the_limit(fleet):
    _, reqs, _, answers = fleet
    chunks = answers[2]["chunks"]
    assert chunks.shape[1] == 2 and len(chunks) >= 5 * len(reqs)
    assert answers[2]["load_seed"] == reqs[0].env_seed
    assert _gap(answers) <= LIMIT


def test_one_chunk_off_reads_above_the_limit(fleet):
    reqs, sessions, report = fleet[3]
    chunks = report["chunks"].copy()
    chunks[len(chunks) // 2, 1] += 0.01
    gap = _gap((reqs, sessions, dict(report, chunks=chunks)))
    assert gap == pytest.approx(0.01, abs=1e-12) and gap > LIMIT


def test_a_load_per_tenant_reads_above_the_limit(fleet):
    from repro.core import run_fleet
    from repro.netsim.testbeds import make_traffic

    db, reqs, engine, _ = fleet
    own = [dataclasses.replace(r, traffic=make_traffic(CONFIG["testbed"],
                                                       seed=r.env_seed))
           for r in reqs]
    assert _gap(fleet_load.plain(reqs, run_fleet(db, own, engine))) > 0.01


def test_another_seed_reads_above_the_limit(fleet):
    reqs, sessions, report = fleet[3]
    seed = fleet[1][1].env_seed  # request 1's, not request 0's
    assert _gap((reqs, sessions, dict(report, load_seed=seed))) > LIMIT


def test_no_chunk_reads_nothing():
    assert ref.load_gap(np.zeros((0, 2)), LOAD, 3) == 0.0


def test_setup_without_a_load_block_fails_at_once():
    cfg = {k: v for k, v in CONFIG.items() if k != "load"}
    with pytest.raises(ValueError, match="states no load block"):
        fleet_load.setup(cfg, TRAFFIC, 1, None)


def test_the_drivers_controls_add_the_three_of_the_shared_load():
    from benchmarks.chip.drivers import fleet

    assert fleet_load.CONTROLS == fleet.CONTROLS + [
        {"fault": "load_unshared"}, {"per_tenant_load": 1},
        {"load_float32": 1}]


def test_the_reference_in_float32_reads_above_the_limit(fleet):
    reqs, sessions, report = fleet[3]
    chunks = report["chunks"].copy()
    chunks[:, 1] = ref.link_load(LOAD, report["load_seed"], chunks[:, 0],
                                 np.float32)
    gap = _gap((reqs, sessions, dict(report, chunks=chunks)))
    assert 1e3 * LIMIT < gap < 1e-5
