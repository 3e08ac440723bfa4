"""Open-loop arrivals in the fleet driver, the reference's admission
guarantees, and a second fleet deployment that joins the cell tests by
files alone.

The second deployment is the DIDCLAB LAN of arXiv:1707.09455 Table 1
(1 Gbps, 0.2 ms RTT, 10 MB buffers, 720 Mbps disks) under Poisson arrivals
over the testbed's diurnal load.  Its
configuration, traffic mix, driver (a copy of the fleet driver under a new
name) and test-support module are written to a temporary directory, and
the cell is put through the checks every cell of ``BENCHMARK.json`` passes.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import pathlib
import shutil
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from cellcheck import (  # noqa: E402
    CHIP, SUPPORT, Staged, check_control, check_end_to_end, check_fault,
    check_traced, fault_cases, load_support, no_compile_cache, with_parked)

from benchmarks.chip import gen  # noqa: E402
from benchmarks.chip.drivers import fleet  # noqa: E402
from benchmarks.chip.reference import fleet as ref  # noqa: E402

XSEDE = json.loads((CHIP / "configs" / "xsede-fleet.json").read_text())
FLEET_2K = json.loads((CHIP / "traffic" / "fleet-2k.json").read_text())

# --------------------------------------------------------------------- #
# the second deployment, as new files
# --------------------------------------------------------------------- #
DRIVER = "fleet_arrivals"
CELL = "fleet_arrivals.didclab-poisson"
DIDCLAB = {
    **XSEDE,
    "name": "didclab-lan",
    "source": "arXiv:1707.09455 Table 1 DIDCLAB LAN, WS-10 to Evenstar",
    "deployment": "one DIDCLAB LAN link shared by open-loop arrivals of "
                  "tuned transfers",
    "testbed": "didclab",
    "link": {"bandwidth_mbps": 1000.0, "rtt_s": 0.0002,
             "tcp_buffer_mb": 10.0, "disk_mbps": 720.0},
    "history": {"days": 1.0, "transfers_per_day": 220, "seed": 1707094551},
    "guarantees": XSEDE["guarantees"],
    "assumed": [
        "knowledge: one OfflineDB mined from 1 day x 220 transfers/day of "
        "simulated didclab history, drawn from the fixed history seed, "
        "frozen during the window",
        "the testbed's diurnal external load during the fleets (Sec. 4.2: "
        "a sharp 11:00-15:00 peak on the lab LAN)",
        "open-loop Poisson arrivals from 08:00 at the traffic's offered "
        "share of the lesser of bandwidth and disk rate",
        "parameter domain cc, p, pp in 1..16 (Sec. 3.1.2 integer lattice)",
    ],
}
POISSON = {
    **FLEET_2K,
    "driver": DRIVER,
    "constant_load": None,
    "start_clock_s": 28800.0,
    "arrivals": {"offered_load": 0.7},
}


def _second(bench: dict) -> dict:
    """``bench`` with the DIDCLAB configuration and cell added, and the cell
    listed wherever the fleet cell is."""
    bench["configs"].append({
        "name": "didclab-lan", "source": DIDCLAB["source"],
        "file": "benchmarks/chip/configs/didclab-lan.json", "reduced": [],
        "why": "one LAN link whose disks bound it, under open-loop arrivals"})
    bench["workloads"].append({
        "name": CELL, "config": "didclab-lan", "traffic": "poisson-2k",
        "chips": 1, "why": "Poisson arrivals at 70 % of the disk rate"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "fleet.xsede-2k" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    return bench


@pytest.fixture
def second(tmp_path, monkeypatch):
    """The benchmark staged with the DIDCLAB deployment added by files."""
    no_compile_cache(monkeypatch)
    st = Staged.copy(tmp_path, with_parked())
    (st.here / "configs" / "didclab-lan.json").write_text(json.dumps(DIDCLAB))
    (st.here / "traffic" / "poisson-2k.json").write_text(json.dumps(POISSON))
    shutil.copy(st.here / "drivers" / "fleet.py",
                st.here / "drivers" / f"{DRIVER}.py")
    st.support_dir = tmp_path / "support"
    shutil.copytree(SUPPORT, st.support_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(SUPPORT / "fleet.py", st.support_dir / f"{DRIVER}.py")
    st.write(_second(st.bench))
    st.shrink(CELL)
    return st


def _record_fleets(monkeypatch) -> list:
    """Every ``run_fleet`` call's requests, engine settings and report."""
    import repro.core as core

    orig, calls = core.run_fleet, []

    def recorded(db, reqs, engine=None):
        rep = orig(db, reqs, engine)
        calls.append((reqs, engine, rep))
        return rep

    monkeypatch.setattr(core, "run_fleet", recorded)
    return calls


SECOND_CHECKS = (["end_to_end", "traced", "control"]
                 + load_support("fleet").FAULTS)


@pytest.mark.parametrize("check", SECOND_CHECKS)
def test_second_fleet_deployment_joins_by_files_alone(second, check,
                                                      tmp_path, monkeypatch):
    if check == "traced":
        check_traced(second, CELL, tmp_path, monkeypatch)
    elif check == "control":
        check_control(second, CELL)
    elif check != "end_to_end":
        check_fault(second, CELL, check, monkeypatch)
    else:
        calls = _record_fleets(monkeypatch)
        line = check_end_to_end(second, CELL)
        assert len(line["checks"]) == 9
        assert line["checks"]["early_admits"]["value"] == 0
        assert line["checks"]["queue_gap"]["value"] == 0
        assert calls and all(e.testbed == "didclab" for _, e, _ in calls)
        slot_free = queued = 0
        for reqs, _, rep in calls:
            arrival = [r.start_clock_s for r in reqs]
            assert len(set(arrival)) > 1
            assert all(s.report.achieved_mbps <= 1000.0
                       for s in rep.sessions)
            order = sorted(range(len(reqs)), key=lambda i: (arrival[i], i))
            later = set(order[rep.admitted_concurrency:])
            first = [s for s in rep.sessions if s.attempt == 0]
            # past the first cap, a request admitted when it arrived found
            # a slot free, and one admitted later waited in the queue
            slot_free += sum(1 for s in first if s.request_index in later
                             and s.admit_s == arrival[s.request_index])
            queued += sum(1 for s in first
                          if s.admit_s > arrival[s.request_index])
        assert slot_free > 0 and queued > 0


def test_a_driver_without_test_support_fails_its_cell_not_collection(
        tmp_path, monkeypatch):
    no_compile_cache(monkeypatch)
    st = Staged.copy(tmp_path, with_parked())
    (st.here / "traffic" / "bare.json").write_text(
        json.dumps({**FLEET_2K, "driver": "fleet_bare", "sessions": 24}))
    shutil.copy(st.here / "drivers" / "fleet.py",
                st.here / "drivers" / "fleet_bare.py")
    bench = st.bench
    bench["workloads"].append({"name": "fleet_bare.x", "config": "xsede-fleet",
                               "traffic": "bare", "chips": 1, "why": "test"})
    st.write(bench)
    assert fault_cases(bench, st.here)[-1] == ("fleet_bare.x",
                                               "no-test-support")
    with pytest.raises(pytest.fail.Exception,
                       match="'fleet_bare' has no test-support module"):
        check_end_to_end(st, "fleet_bare.x")


# --------------------------------------------------------------------- #
# the existing cell's draws do not move
# --------------------------------------------------------------------- #
# taken from the driver before arrivals were added: the requests of six
# seeds of fleet-2k, and the engine settings
PARENT_REQUESTS = ("f6d73d3df7a0595b0463bd28fe38fbf740e91d0e7c9ff771c3dcbf01"
                   "aa4d88fd")
PARENT_ENGINE = {
    "bulk_chunks": "8", "contention": "'auto'", "engine": "'vectorized'",
    "faults": "None", "knowledge": "None", "max_concurrent": "None",
    "max_samples": "3", "n_shards": "None", "overcommit": "2.0",
    "recovery": "None", "refresh": "None", "reprobe_interval_s": "5.0",
    "score_vs_single": "False", "shard_window_s": "None",
    "testbed": "'xsede'", "use_pallas": "False", "z": "2.0"}


def test_fleet_2k_requests_and_engine_config_are_the_parents():
    h = hashlib.sha256()
    for seed in (0, 17, 2**31 + 12345, 4052739537, 2**62 - 1, 2**63 - 1):
        for r in fleet.requests(XSEDE, FLEET_2K, seed):
            h.update(json.dumps([
                r.dataset.name, r.dataset.file_class,
                repr(r.dataset.avg_file_mb), r.dataset.n_files, r.env_seed,
                repr(r.start_clock_s), repr(r.constant_load),
                repr(r.traffic)]).encode())
    assert h.hexdigest() == PARENT_REQUESTS
    engine = dataclasses.asdict(fleet.engine_config(XSEDE))
    assert {k: repr(v) for k, v in engine.items()} == PARENT_ENGINE


# --------------------------------------------------------------------- #
# arrivals
# --------------------------------------------------------------------- #
def test_mean_request_size_is_the_drawn_mean():
    classes = FLEET_2K["classes"]
    drawn = [avg * nf for _, avg, nf in gen.datasets(XSEDE, classes, 60000, 5)]
    assert np.mean(drawn) == pytest.approx(
        gen.mean_request_mb(XSEDE, classes), rel=0.03)


@pytest.mark.parametrize("rate", [0.5, 0.002])
def test_poisson_arrivals_keep_their_rate(rate):
    t = gen.poisson_arrivals(100000, rate, 9)
    assert np.all(np.diff(t) >= 0) and t[0] >= 0
    assert len(t) / t[-1] == pytest.approx(rate, rel=0.03)
    assert np.array_equal(t, gen.poisson_arrivals(100000, rate, 9))
    # counts per window of five mean gaps: variance equals the mean
    counts = np.bincount((t // (5.0 / rate)).astype(int))[:-1]
    assert 0.9 < counts.var() / counts.mean() < 1.1


def test_arrivals_offer_the_load_of_the_link_and_keep_other_draws():
    n = 4000
    big = {**POISSON, "sessions": n}
    at = fleet.arrivals(DIDCLAB, big, n, 3)
    assert at[0] >= POISSON["start_clock_s"]
    rate = n / (at[-1] - POISSON["start_clock_s"])
    # the disks, 720 Mbit/s, bound the 1 Gbit/s link
    want = 0.7 * 720.0 / (8.0 * gen.mean_request_mb(DIDCLAB,
                                                      POISSON["classes"]))
    assert rate == pytest.approx(want, rel=0.05)
    reqs = fleet.requests(DIDCLAB, big, 3)
    queued = fleet.requests(DIDCLAB, {**big, "arrivals": None}, 3)
    assert [r.start_clock_s for r in reqs] == list(at)
    assert all(r.constant_load is None for r in reqs)
    # arrivals come from a stream of their own: datasets and seeds as queued
    assert [(r.dataset, r.env_seed) for r in reqs] == [
        (r.dataset, r.env_seed) for r in queued]
    assert {r.start_clock_s for r in queued} == {POISSON["start_clock_s"]}


@pytest.mark.parametrize("n", [3, 4])
def test_queue_jumped_needs_two_queued_requests(n):
    # cap 2: three requests leave one queued, four leave two
    arrival = [0.0, 0.0, 1.0, 5.0][:n]
    if n == 4:
        assert fleet.queued_pair(arrival, 2) == (2, 3)
        return
    with pytest.raises(ValueError, match="no queue to jump"):
        fleet.queued_pair(arrival, 2)
    reqs, sess = _fleet(arrival, arrival, [10.0, 3.0, 7.0])
    with pytest.raises(ValueError, match="no queue to jump"):
        fleet._planted((reqs, sess, {"admitted_concurrency": 2}),
                       "queue_jumped", 1000.0, 16)


# --------------------------------------------------------------------- #
# the reference's admission guarantees, on hand-made fleets
# --------------------------------------------------------------------- #
def _fleet(arrival, admits, ends, attempts=None):
    reqs = [{"avg_file_mb": 1.0, "n_files": 1, "arrival_s": a}
            for a in arrival]
    sess = [{"request": i, "attempt": 0, "admit_s": a, "end_s": e}
            for i, (a, e) in enumerate(zip(admits, ends))]
    return reqs, sess + (attempts or [])


# cap 2: requests 0 and 1 arrive at 0, 2 at 1, 3 at 5; 1 ends at 3, so 2
# is admitted then; 2 ends at 7, before 0 at 10, so 3 is admitted at 7
ARRIVAL = [0.0, 0.0, 1.0, 5.0]
ENDS = [10.0, 3.0, 7.0, 9.0]


def test_fifo_admissions_hold_for_first_come_first_served():
    reqs, sess = _fleet(ARRIVAL, [0.0, 0.0, 3.0, 7.0], ENDS)
    assert ref.admission(reqs, sess, 2) == {"early_admits": 0.0,
                                            "queue_gap": 0.0}
    # a free slot waits for the next arrival
    reqs, sess = _fleet([0.0, 0.0, 1.0, 50.0], [0.0, 0.0, 3.0, 50.0],
                        [10.0, 3.0, 7.0, 60.0])
    assert ref.admission(reqs, sess, 2)["queue_gap"] == 0.0
    # a re-admission of a killed request queues behind all of them and
    # moves no first admission
    reqs, sess = _fleet(ARRIVAL, [0.0, 0.0, 3.0, 7.0], ENDS, attempts=[
        {"request": 1, "attempt": 1, "admit_s": 9.0, "end_s": 12.0}])
    assert ref.admission(reqs, sess, 2) == {"early_admits": 0.0,
                                            "queue_gap": 0.0}


def test_an_early_admission_is_counted():
    reqs, sess = _fleet(ARRIVAL, [0.0, 0.0, 3.0, 4.0], ENDS)
    got = ref.admission(reqs, sess, 2)
    assert got["early_admits"] == 1.0
    assert got["queue_gap"] == 3.0


def test_a_jumped_queue_reads_a_gap():
    # 3, arrived at 2, served in 2's turn at 3, and 2 in 3's at 7
    reqs, sess = _fleet([0.0, 0.0, 1.0, 2.0], [0.0, 0.0, 7.0, 3.0], ENDS)
    got = ref.admission(reqs, sess, 2)
    assert got["early_admits"] == 0.0
    assert got["queue_gap"] == 4.0
    # a cap reported one low reads a gap too
    reqs, sess = _fleet(ARRIVAL, [0.0, 0.0, 3.0, 7.0], ENDS)
    assert ref.admission(reqs, sess, 1)["queue_gap"] > 0


def test_ties_at_equal_ends_and_arrivals():
    # 0 and 1 end together: 2 and 3 both take a freed slot at 3
    reqs, sess = _fleet([0.0, 0.0, 1.0, 1.0], [0.0, 0.0, 3.0, 3.0],
                        [3.0, 3.0, 8.0, 9.0])
    assert ref.admission(reqs, sess, 2)["queue_gap"] == 0.0
    # 2 and 3 arrive together: the lower index goes first
    reqs, sess = _fleet([0.0, 0.0, 1.0, 1.0], [0.0, 0.0, 3.0, 8.0],
                        [3.0, 8.0, 9.0, 9.0])
    assert ref.admission(reqs, sess, 2)["queue_gap"] == 0.0
    reqs, sess = _fleet([0.0, 0.0, 1.0, 1.0], [0.0, 0.0, 8.0, 3.0],
                        [3.0, 8.0, 9.0, 9.0])
    assert ref.admission(reqs, sess, 2)["queue_gap"] == 5.0

