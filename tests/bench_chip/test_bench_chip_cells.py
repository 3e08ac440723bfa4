"""The benchmark's cells, driven in-process at a tiny size on the CPU.

Each test copies the benchmark's data files into a temporary directory,
shrinks the traffic, and runs the harness there with the TPU check waived
by the test.  Traced runs read a trace recorded on a TPU v5e in place of
the CPU's, so every per-layer reader has something to read.  The cells
kept out of ``BENCHMARK.json`` (``benchmarks/chip/parked.json``) are run
alongside, from a ``BENCHMARK.json`` that holds both.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import (  # noqa: E402
    clock, control, harness, roofline, trace)


def _with_parked() -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    parked = json.loads((ROOT / "benchmarks" / "chip" / "parked.json")
                        .read_text())
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        bench[key] = bench[key] + parked[key]
    return bench


BENCH = _with_parked()
CELLS = [w["name"] for w in BENCH["workloads"]]
SEED = 2**31 + 12345
TINY = {
    "discovery-1m": {"rows": 8192, "logs": 2, "m_range": [4, 6]},
    "fleet-2k": {"sessions": 24},
}
TINY_CONFIG = {"xsede-fleet": {"history": {"days": 1.0,
                                           "transfers_per_day": 120,
                                           "seed": 17}}}
RECORDED = {"discovery": "discovery.xplane.pb.gz",
            "fleet": "fleet.xplane.pb.gz"}


def _cell(name: str) -> dict:
    return next(w for w in BENCH["workloads"] if w["name"] == name)


@pytest.fixture
def here(tmp_path, monkeypatch):
    """The benchmark's files, with tiny traffic, in a temporary directory."""
    dst = tmp_path / "chip"
    for sub in ("configs", "traffic", "metrics", "drivers"):
        shutil.copytree(ROOT / "benchmarks" / "chip" / sub, dst / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for kind, table in (("traffic", TINY), ("configs", TINY_CONFIG)):
        for name, over in table.items():
            p = dst / kind / f"{name}.json"
            p.write_text(json.dumps({**json.loads(p.read_text()), **over}))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(BENCH))
    # the suite's process shares one JAX config: keep the persistent cache
    # out of it
    monkeypatch.setattr(clock, "enable_compile_cache", lambda: "off")
    return dst


@pytest.fixture
def recorded_trace(tmp_path, monkeypatch):
    """Route the harness's trace reading to a trace recorded on the chip,
    and its peaks to the chip it was recorded on."""
    v5e = roofline.peaks("TPU v5 lite")
    monkeypatch.setattr(roofline, "peaks", lambda kind: v5e)

    def use(driver: str):
        xp = tmp_path / "recorded.xplane.pb"
        xp.write_bytes(gzip.decompress((DATA / RECORDED[driver]).read_bytes()))
        monkeypatch.setattr(trace, "find_xplane", lambda _: str(xp))
    return use


def _run(here, name, traced=False, seconds=0.4, bench_path=None):
    return harness.run_cell(name, SEED, seconds, traced, here=here,
                            bench_path=bench_path or here.parent
                            / "BENCHMARK.json",
                            require_tpu=False)


def _driver(name: str) -> str:
    traffic = json.loads((ROOT / "benchmarks" / "chip" / "traffic"
                          / f"{_cell(name)['traffic']}.json").read_text())
    return traffic["driver"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_prints_its_end_to_end_metrics(here, name):
    line = _run(here, name)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    e2e, _ = harness.cell_metrics(BENCH, name)
    assert set(line["metrics"]) == {m["name"] for m in e2e}
    assert "setup_s" in line["metrics"]
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] >= 1 and dev["kind"]
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_traced_cell_prints_every_per_layer_metric(here, name,
                                                   recorded_trace):
    recorded_trace(_driver(name))
    line = _run(here, name, traced=True)
    _, layer = harness.cell_metrics(BENCH, name)
    assert set(line["metrics"]) == {m["name"] for m in layer}
    dev = line["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    bd = line["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert line["correct"] is True
    for name_, m in line["metrics"].items():
        if name_.endswith("_roofline") or name_.startswith("idle_share"):
            assert 0 <= m["value"] <= 100


def test_a_cell_config_and_metric_are_added_by_files_alone(here, tmp_path,
                                                           recorded_trace):
    """A new configuration, traffic mix, cell and per-layer metric, defined
    in files only: the harness finds each by its name."""
    cfg = json.loads((here / "configs" / "table1-multinet.json").read_text())
    cfg["testbeds"] = {k: v for k, v in cfg["testbeds"].items()
                       if k != "didclab"}
    (here / "configs" / "two-pairs.json").write_text(json.dumps(cfg))
    mix = json.loads((here / "traffic" / "discovery-1m.json").read_text())
    mix.update(rows=6000, m_range=[3, 5])
    (here / "traffic" / "discovery-6k.json").write_text(json.dumps(mix))
    (here / "metrics" / "fits.discovery.py").write_text(
        "def read(ctx):\n    return float(ctx['result']['units'])\n")
    bench = _with_parked()
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
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    line = _run(here, "discovery.two-6k", bench_path=path)
    assert set(line["metrics"]) == {"setup_s", "discovery_s"}
    assert line["correct"] is True
    recorded_trace("discovery")
    line = _run(here, "discovery.two-6k", traced=True, bench_path=path)
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
def test_control_fails_a_compared_number(here, name):
    traffic = json.loads((here / "traffic" /
                          f"{_cell(name)['traffic']}.json").read_text())
    limits = {k: v for k, v in traffic["limits"].items() if v is not None}
    out = next(control.readings(name, [SEED], 1, 0.4, here=here,
                                bench_path=here.parent / "BENCHMARK.json",
                                require_tpu=False))
    assert all(out["program"][k] <= v for k, v in limits.items())
    failing = {}
    for key, got in out.items():
        if key.startswith("control ") and isinstance(got, dict):
            failing[key] = [k for k, v in limits.items()
                            if got.get(k, float("inf")) > v]
    # the control that the limits are set against fails at least one
    assert any(failing.values()), failing


# --------------------------------------------------------------------- #
# faults planted under the timed path: each must turn `correct` false
# --------------------------------------------------------------------- #
def _discovery_fault(kind, monkeypatch):
    import numpy as np

    from repro.core import clustering

    if kind == "state_unchanged":
        # every update step hands its centroids back unchanged: the sweep
        # returns its seeds and the refinement makes no step
        _, refine = clustering._jax_sweeps()
        monkeypatch.setattr(clustering, "_jax_sweeps", lambda: (
            lambda X, C0, b: C0,
            lambda Xc, wc, C0, steps: refine(Xc, wc, C0, steps[:0])))
        return
    orig = clustering.fit_clusters

    def broken(X, **kw):
        if kind == "half_batch":
            cm = orig(X[: len(X) // 2], **kw)
            cm.labels = cm.assign_many(X)
            return cm
        cm = orig(X, **kw)
        if kind == "labels_shifted":
            # the label output read one row off, as a wrong slice would
            cm.labels = np.roll(cm.labels, 1)
            return cm
        cm.centroids = cm.centroids.copy()
        cm.centroids[0, 2] += 1e-2 * max(abs(cm.centroids[0, 2]), 1.0)
        return cm

    monkeypatch.setattr(clustering, "fit_clusters", broken)
    assert np  # the planted faults act on numpy results


def _fleet_fault(kind, monkeypatch):
    import repro.core as core

    orig = core.run_fleet
    first = {}

    def _session(rep, i, **report):
        s = rep.sessions[i]
        rep.sessions[i] = dataclasses.replace(
            s, report=dataclasses.replace(s.report, **report))

    def broken(db, reqs, config=None):
        if kind == "state_unchanged":
            if "report" not in first:
                first["report"] = orig(db, reqs, config)
            return first["report"]
        if kind == "half_batch":
            return orig(db, reqs[::2], config)
        if kind == "cap_shifted":
            # admission at one session more than the scoring gives
            cap = orig(db, reqs, config).admitted_concurrency
            return orig(db, reqs,
                        dataclasses.replace(config, max_concurrent=cap + 1))
        rep = orig(db, reqs, config)
        if kind == "cap_reported_low":
            rep.admitted_concurrency -= 1
        elif kind == "over_link":
            _session(rep, 0, achieved_mbps=1.01e4)
        elif kind == "params_shifted":
            for i, s in enumerate(rep.sessions):
                prm = s.report.params
                _session(rep, i, params=dataclasses.replace(
                    prm, cc=prm.cc + 1 if prm.cc < 16 else prm.cc - 1))
        else:
            _session(rep, 0, moved_mb=0.5 * rep.sessions[0].report.moved_mb)
        return rep

    monkeypatch.setattr(core, "run_fleet", broken)


FAULTS = {
    "discovery": ["state_unchanged", "half_batch", "answer_altered",
                  "labels_shifted"],
    "fleet": ["state_unchanged", "half_batch", "answer_altered",
              "cap_shifted", "cap_reported_low", "over_link",
              "params_shifted"],
}
CELL_FAULTS = [(name, kind) for name in CELLS for kind in FAULTS[_driver(name)]]


@pytest.mark.parametrize("name,kind", CELL_FAULTS,
                         ids=[f"{n}-{k}" for n, k in CELL_FAULTS])
def test_planted_fault_makes_the_run_incorrect(here, name, kind,
                                               monkeypatch):
    plant = {"discovery": _discovery_fault, "fleet": _fleet_fault}
    # the warm-up in set-up runs unbroken; the fault sits under the window
    real_window = harness.load_module

    def load(path):
        mod = real_window(path)
        if path.parent.name == "drivers":
            win = mod.window

            def window(state, seconds, spans):
                plant[_driver(name)](kind, monkeypatch)
                return win(state, seconds, spans)

            mod.window = window
        return mod

    monkeypatch.setattr(harness, "load_module", load)
    line = _run(here, name, seconds=0.3)
    assert line["correct"] is False, line["checks"]
