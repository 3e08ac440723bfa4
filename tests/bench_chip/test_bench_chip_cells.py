"""The benchmark's cells, driven in-process at a tiny size on the CPU.

Each test stages the benchmark's data files in a temporary directory,
shrinks each cell by its driver's test support, and runs the harness there
with the TPU check waived by the test (``cellcheck``).  Nothing here is
keyed by a cell, configuration, traffic or driver name: a cell joins every
test by its files and its driver's test-support module alone.  The cells
kept out of ``BENCHMARK.json`` (``benchmarks/chip/parked.json``) are run
alongside, from a ``BENCHMARK.json`` that holds both.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from cellcheck import (  # noqa: E402
    ROOT, SEED, Staged, check_control, check_end_to_end, check_fault,
    check_traced, fault_cases, no_compile_cache, use_recorded_trace,
    with_parked)

BENCH = with_parked()
CELLS = [w["name"] for w in BENCH["workloads"]]
CELL_FAULTS = fault_cases(BENCH)


@pytest.fixture
def staged(tmp_path, monkeypatch):
    """The benchmark's files, every cell shrunk, in a temporary directory."""
    no_compile_cache(monkeypatch)
    return Staged.copy(tmp_path, BENCH)


@pytest.mark.parametrize("name", CELLS)
def test_cell_prints_its_end_to_end_metrics(staged, name):
    check_end_to_end(staged, name)


@pytest.mark.parametrize("name", CELLS)
def test_traced_cell_prints_every_per_layer_metric(staged, name, tmp_path,
                                                   monkeypatch):
    check_traced(staged, name, tmp_path, monkeypatch)


def test_a_cell_config_and_metric_are_added_by_files_alone(staged, tmp_path,
                                                           monkeypatch):
    """A new configuration, traffic mix, cell and per-layer metric, defined
    in files only: the harness finds each by its name."""
    here = staged.here
    cfg = json.loads((here / "configs" / "table1-multinet.json").read_text())
    cfg["testbeds"] = {k: v for k, v in cfg["testbeds"].items()
                       if k != "didclab"}
    (here / "configs" / "two-pairs.json").write_text(json.dumps(cfg))
    mix = json.loads((here / "traffic" / "discovery-1m.json").read_text())
    mix.update(rows=6000, m_range=[3, 5])
    (here / "traffic" / "discovery-6k.json").write_text(json.dumps(mix))
    (here / "metrics" / "fits.discovery.py").write_text(
        "def read(ctx):\n    return float(ctx['result']['units'])\n")
    bench = with_parked()
    bench["workloads"].append({"name": "discovery.two-6k",
                               "config": "two-pairs",
                               "traffic": "discovery-6k", "chips": 1,
                               "why": "test"})
    next(m for m in bench["end_to_end"] if m["name"] == "discovery_s"
         )["workloads"].append("discovery.two-6k")
    bench["per_layer"].append({
        "name": "fits.discovery", "unit": "fits", "better": "higher",
        "source": "program_counter", "layer": "offline discovery",
        "moves": "discovery_s", "workloads": ["discovery.two-6k"]})
    staged.write(bench)
    line = staged.run("discovery.two-6k")
    assert set(line["metrics"]) == {"setup_s", "discovery_s"}
    assert line["correct"] is True
    use_recorded_trace(tmp_path, monkeypatch,
                       staged.support("discovery.two-6k").RECORDED)
    line = staged.run("discovery.two-6k", traced=True)
    assert line["metrics"]["fits.discovery"]["value"] >= 1


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_harness_sets_no_dispatch_option_of_the_program():
    src = "".join(p.read_text() for p in
                  (ROOT / "benchmarks" / "chip").rglob("*.py"))
    for flag in ("use_pallas", "contention=", "n_shards"):
        assert flag not in src


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_a_compared_number(staged, name):
    check_control(staged, name)


# faults planted under the timed path: each must turn `correct` false
@pytest.mark.parametrize("name,kind", CELL_FAULTS,
                         ids=[f"{n}-{k}" for n, k in CELL_FAULTS])
def test_planted_fault_makes_the_run_incorrect(staged, name, kind,
                                               monkeypatch):
    check_fault(staged, name, kind, monkeypatch)
