"""The program's spans and counters reduced per fleet (``program_trace``).

Hand-made events check containment in the window, the union of device
programs inside the scoring spans, and that the six times partition the
window.  ``data/fleet-program.xplane.pb.gz`` is a short traced run of
``fleet.xsede-2k`` recorded on a TPU v5e (``--trace 1 --seconds 3``), and
``data/fleet-program.line.json`` the result line that run printed; the
PR 12 trace ``data/fleet.xplane.pb.gz``, recorded before the program had
spans, reads as nothing.
"""
from __future__ import annotations

import gzip
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness, program_trace, trace  # noqa: E402

TIMES = ("scoring_wall_ms", "admit_ms", "sampler_ms", "netsim_ms",
         "engine_self_ms", "outside_run_ms")
READERS = TIMES + ("scoring_in_span_ms", "events")
Events = program_trace.ProgramEvents


def _run(start, dur, fleet, **stats):
    return ("fleet.run", start, dur, {"fleet": fleet, **stats})


def _score(start, dur, fleet):
    return ("fleet.score", start, dur, {"fleet": fleet, "clusters": 3})


def _counters(events, admit, step, netsim):
    return dict(events=events, admissions=10, admit_ns=admit, step_ns=step,
                netsim_ns=netsim)


# two fleets in a window of 1,000 ns; a third run starts inside the window
# and ends outside it, a fourth lies before it
HAND = Events(
    spans=[
        _run(100, 300, 0, **_counters(40, 60, 150, 100)),
        _score(110, 20, 0),
        _run(500, 400, 1, **_counters(60, 80, 200, 120)),
        _score(510, 30, 1),
        _run(950, 100, 2, **_counters(5, 1, 1, 1)),
        _run(-300, 200, 3, **_counters(5, 1, 1, 1)),
        ("fleet.other", 0, 5, {}),
    ],
    window=(0.0, 1000.0),
    programs=[[
        (112, 4), (114, 4),  # overlapping: 6 ns once
        (128, 6),  # half inside the first score span: 2 ns
        (300, 50),  # outside every score span
        (515, 5), (535, 10),  # 5 ns, and 5 of 10 ns inside the second
    ]],
)


def test_fleets_are_the_run_spans_inside_the_window():
    f = program_trace.reduce(HAND)
    assert f.fleets == 2
    assert f.window_ns == 1000.0
    assert f.run_ns == 700.0
    assert f.score_ns == 50.0
    assert f.counters["events"] == 100.0
    assert f.counters["admit_ns"] == 140.0


def test_device_scoring_is_the_union_of_programs_inside_the_score_spans():
    f = program_trace.reduce(HAND)
    assert f.score_device_ns == 6.0 + 2.0 + 5.0 + 5.0


def test_hand_made_metrics_per_fleet():
    m = program_trace.reduce(HAND).metrics()
    assert m == pytest.approx({
        "scoring_wall_ms": 25e-6,
        "scoring_in_span_ms": 9e-6,
        "outside_run_ms": 150e-6,
        "events": 50.0,
        "admit_ms": 70e-6,
        "sampler_ms": 65e-6,
        "netsim_ms": 110e-6,
        "engine_self_ms": (700 - 50 - 140 - 350) / 2 * 1e-6,
    }, rel=1e-12)


def test_the_six_times_partition_the_window():
    m = program_trace.reduce(HAND).metrics()
    assert sum(m[k] for k in TIMES) == pytest.approx(1000e-6 / 2, rel=1e-12)


def test_runs_without_counters_report_the_span_times_alone():
    ev = Events([_run(100, 300, 0), _score(110, 20, 0)], (0.0, 1000.0),
                [[(112, 4)]])
    assert set(program_trace.reduce(ev).metrics()) == {
        "scoring_wall_ms", "scoring_in_span_ms", "outside_run_ms"}


@pytest.mark.parametrize("ev", [
    Events([_run(100, 300, 0)], None, [[]]),
    Events([_score(110, 20, 0)], (0.0, 1000.0), [[]]),
    Events([_run(-300, 200, 0)], (0.0, 1000.0), [[]]),
], ids=["no-window", "no-run", "run-outside"])
def test_nothing_to_read_reduces_to_none(ev):
    assert program_trace.reduce(ev) is None


def test_an_untraced_run_reads_nothing():
    assert program_trace.metric({"summary": None}, "netsim_ms") is None


def _readers():
    return {name: harness.load_module(
        ROOT / "benchmarks" / "chip" / "metrics" / f"{name}.fleet.py")
        for name in READERS}


def _unpack(tmp_path, name: str) -> pathlib.Path:
    xp = tmp_path / "plugins" / "profile" / "t" / "t.xplane.pb"
    xp.parent.mkdir(parents=True)
    xp.write_bytes(gzip.decompress((DATA / name).read_bytes()))
    return xp


def test_a_trace_of_the_parent_program_reads_nothing(tmp_path, monkeypatch):
    _unpack(tmp_path, "fleet.xplane.pb.gz")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    ctx = {"summary": object()}
    for name, reader in _readers().items():
        assert reader.read(ctx) is None, name


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The recorded run: its result line, trace events and reduction."""
    tmp = tmp_path_factory.mktemp("program")
    xp = _unpack(tmp, "fleet-program.xplane.pb.gz")
    line = json.loads((DATA / "fleet-program.line.json").read_text())
    fleets = program_trace.reduce(program_trace.load(str(xp)))
    return tmp, line, fleets


def test_recorded_fleets_are_the_runs_units(recorded):
    _, line, fleets = recorded
    assert fleets.fleets == line["diagnostics"]["units"] >= 2


def test_recorded_times_add_up_to_the_window_per_fleet(recorded):
    _, line, fleets = recorded
    m = fleets.metrics()
    window_ms = 1e3 * line["device"]["window_s"] / line["diagnostics"]["units"]
    assert sum(m[k] for k in TIMES) == pytest.approx(window_ms, rel=0.02)
    assert all(m[k] > 0 for k in TIMES)


def test_recorded_scoring_in_span_matches_scoring_by_name(recorded):
    tmp, line, fleets = recorded
    summary = trace.summarize(trace.load_events(trace.find_xplane(str(tmp))))
    by_name = harness.load_module(
        ROOT / "benchmarks" / "chip" / "metrics" / "scoring_ms.fleet.py")
    ms = by_name.read({"summary": summary, "result": {
        "units": line["diagnostics"]["units"]}})
    assert fleets.metrics()["scoring_in_span_ms"] == pytest.approx(ms,
                                                                   rel=0.05)


def test_recorded_readers_match_the_printed_line(recorded, monkeypatch):
    tmp, line, _ = recorded
    monkeypatch.setattr(harness, "TRACE_DIR", tmp)
    ctx = {"summary": object()}
    for name, reader in _readers().items():
        printed = line["metrics"][f"{name}.fleet"]["value"]
        assert reader.read(ctx) == pytest.approx(printed, rel=1e-12), name
