"""Checks that every benchmark cell passes, driven in-process at a tiny size
on the CPU, found by the cell's driver alone.

A benchmark is staged (:class:`Staged`) by copying its data files and
drivers into a temporary directory beside a ``BENCHMARK.json``.  What a
test needs to know of a driver sits in its test-support module,
``drivers/<driver>.py`` beside this file: ``shrink(config, traffic)``, the
tiny size; ``FAULTS`` and ``plant(kind, monkeypatch, config)``, the faults
planted under its timed path; ``RECORDED``, the trace recorded on the chip
(under ``data/``) that its traced runs read in place of the CPU's.  A cell
whose driver has no such module fails each of its tests with a message
that says so.
"""
from __future__ import annotations

import dataclasses
import gzip
import importlib.util
import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
SUPPORT = pathlib.Path(__file__).resolve().parent / "drivers"
CHIP = ROOT / "benchmarks" / "chip"
SEED = 2**31 + 12345
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import clock, control, harness, roofline, trace  # noqa: E402,I001


def with_parked() -> dict:
    """``BENCHMARK.json`` with the cells kept out of it
    (``benchmarks/chip/parked.json``) added back."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    parked = json.loads((CHIP / "parked.json").read_text())
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        bench[key] = bench[key] + parked[key]
    return bench


def support_path(driver: str, where: pathlib.Path = SUPPORT) -> pathlib.Path:
    return where / f"{driver}.py"


def load_support(driver: str, where: pathlib.Path = SUPPORT):
    """The test-support module of ``driver``; fails the calling test where
    there is none."""
    path = support_path(driver, where)
    if not path.is_file():
        pytest.fail(f"driver {driver!r} has no test-support module: add "
                    f"{path.name} to {where} with shrink, FAULTS, plant and "
                    f"RECORDED")
    spec = importlib.util.spec_from_file_location(
        "bench_support_" + driver.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver_of(bench: dict, name: str, chip: pathlib.Path = CHIP) -> str:
    """The driver that cell ``name``'s traffic names."""
    cell = next(w for w in bench["workloads"] if w["name"] == name)
    traffic = json.loads((chip / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    return traffic["driver"]


def fault_cases(bench: dict, chip: pathlib.Path = CHIP
                ) -> list[tuple[str, str]]:
    """``(cell, fault)`` of every cell's planted faults; a cell whose driver
    has no test-support module gets one case, which fails."""
    out = []
    for w in bench["workloads"]:
        driver = driver_of(bench, w["name"], chip)
        if support_path(driver).is_file():
            out += [(w["name"], k) for k in load_support(driver).FAULTS]
        else:
            out.append((w["name"], "no-test-support"))
    return out


@dataclasses.dataclass
class Staged:
    """A benchmark staged in a temporary directory: ``here`` holds its
    data files and drivers, ``here.parent / "BENCHMARK.json"`` defines it."""

    here: pathlib.Path
    bench: dict
    support_dir: pathlib.Path = SUPPORT

    @classmethod
    def copy(cls, tmp_path: pathlib.Path, bench: dict) -> Staged:
        """The benchmark's files in ``tmp_path``, every cell shrunk."""
        here = tmp_path / "chip"
        for sub in ("configs", "traffic", "metrics", "drivers"):
            shutil.copytree(CHIP / sub, here / sub,
                            ignore=shutil.ignore_patterns("__pycache__"))
        st = cls(here, bench)
        st.write(bench)
        for w in bench["workloads"]:
            if support_path(st.driver(w["name"]), st.support_dir).is_file():
                st.shrink(w["name"])
        return st

    @property
    def bench_path(self) -> pathlib.Path:
        return self.here.parent / "BENCHMARK.json"

    def write(self, bench: dict) -> None:
        self.bench = bench
        self.bench_path.write_text(json.dumps(bench))

    def cell(self, name: str) -> dict:
        return next(w for w in self.bench["workloads"] if w["name"] == name)

    def traffic(self, name: str) -> dict:
        return json.loads((self.here / "traffic"
                           / f"{self.cell(name)['traffic']}.json").read_text())

    def config(self, name: str) -> dict:
        return json.loads((self.here / "configs"
                           / f"{self.cell(name)['config']}.json").read_text())

    def driver(self, name: str) -> str:
        return driver_of(self.bench, name, self.here)

    def support(self, name: str):
        return load_support(self.driver(name), self.support_dir)

    def shrink(self, name: str) -> None:
        """Cut cell ``name``'s configuration and traffic to the tiny size
        its driver's test support gives."""
        cell = self.cell(name)
        cfg, tr = self.support(name).shrink(self.config(name),
                                            self.traffic(name))
        (self.here / "configs" / f"{cell['config']}.json").write_text(
            json.dumps(cfg))
        (self.here / "traffic" / f"{cell['traffic']}.json").write_text(
            json.dumps(tr))

    def run(self, name: str, traced: bool = False,
            seconds: float = 0.4) -> dict:
        return harness.run_cell(name, SEED, seconds, traced, here=self.here,
                                bench_path=self.bench_path,
                                require_tpu=False)


def no_compile_cache(monkeypatch) -> None:
    # the suite's process shares one JAX config: keep the persistent cache
    # out of it
    monkeypatch.setattr(clock, "enable_compile_cache", lambda: "off")


def use_recorded_trace(tmp_path, monkeypatch, recorded: str) -> None:
    """Route the harness's trace reading to trace ``recorded`` of
    ``data/``, taken on the chip, and its peaks to that chip's."""
    v5e = roofline.peaks("TPU v5 lite")
    monkeypatch.setattr(roofline, "peaks", lambda kind: v5e)
    xp = tmp_path / "recorded.xplane.pb"
    xp.write_bytes(gzip.decompress((DATA / recorded).read_bytes()))
    monkeypatch.setattr(trace, "find_xplane", lambda _: str(xp))


# --------------------------------------------------------------------- #
# the checks each cell has to pass
# --------------------------------------------------------------------- #
def check_end_to_end(st: Staged, name: str) -> dict:
    """An untraced run prints the cell's end-to-end metrics and is correct."""
    st.support(name)
    line = st.run(name)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    e2e, _ = harness.cell_metrics(st.bench, name)
    assert set(line["metrics"]) == {m["name"] for m in e2e}
    assert "setup_s" in line["metrics"]
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] >= 1 and dev["kind"]
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["checks"]) == {k for k, v in st.traffic(name)["limits"]
                                   .items() if v is not None}
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]
    return line


def check_traced(st: Staged, name: str, tmp_path, monkeypatch) -> None:
    """A traced run, reading the driver's recorded trace, prints every
    per-layer metric of the cell and is correct."""
    use_recorded_trace(tmp_path, monkeypatch, st.support(name).RECORDED)
    line = st.run(name, traced=True)
    _, layer = harness.cell_metrics(st.bench, name)
    assert set(line["metrics"]) == {m["name"] for m in layer}
    dev = line["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    bd = line["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert line["correct"] is True, line["checks"]
    for name_, m in line["metrics"].items():
        if name_.endswith("_roofline") or name_.startswith("idle_share"):
            assert 0 <= m["value"] <= 100


def check_control(st: Staged, name: str) -> None:
    """``control.readings``: the program is within every limit, and some
    control of the driver fails a compared number."""
    st.support(name)
    limits = {k: v for k, v in st.traffic(name)["limits"].items()
              if v is not None}
    out = next(control.readings(name, [SEED], 1, 0.4, here=st.here,
                                bench_path=st.bench_path,
                                require_tpu=False))
    assert all(out["program"][k] <= v for k, v in limits.items())
    failing = {}
    for key, got in out.items():
        if key.startswith("control ") and isinstance(got, dict):
            failing[key] = [k for k, v in limits.items()
                            if got.get(k, float("inf")) > v]
    # the control that the limits are set against fails at least one
    assert any(failing.values()), failing


def check_fault(st: Staged, name: str, kind: str, monkeypatch) -> None:
    """A fault planted under the window turns ``correct`` false; the
    warm-up in set-up runs unbroken."""
    sup = st.support(name)
    config = st.config(name)
    real_load = harness.load_module

    def load(path):
        mod = real_load(path)
        if path.parent.name == "drivers":
            win = mod.window

            def window(state, seconds, spans):
                sup.plant(kind, monkeypatch, config)
                return win(state, seconds, spans)

            mod.window = window
        return mod

    monkeypatch.setattr(harness, "load_module", load)
    line = st.run(name, seconds=0.3)
    assert line["correct"] is False, line["checks"]
