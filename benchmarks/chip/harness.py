"""The generic runner: one cell, one seed, one process.

Everything a cell needs is found by name.  ``BENCHMARK.json`` names the
cell's configuration and traffic; ``configs/<config>.json`` and
``traffic/<traffic>.json`` hold them as data, and the traffic names its
driver, ``drivers/<driver>.py``, which provides ``setup``, ``window`` and
``check``.  Each per-layer metric of ``BENCHMARK.json`` is read by
``metrics/<metric>.py``, whose ``read(ctx)`` returns a number or ``None``.

A run sets up (timed as ``setup_s``), measures for ``--seconds``, reads the
device's peak memory, frees the program's state, then checks what the
window produced against the plain reference.  ``--trace 0`` reports the
cell's end-to-end metrics; ``--trace 1`` profiles the window and reports
its per-layer metrics with the device's busy time and a breakdown.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import time

from benchmarks.chip import clock, trace

HERE = pathlib.Path(__file__).resolve().parent
ROOT = clock.ROOT
TRACE_DIR = ROOT / ".bench_trace"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_spec(bench_path: pathlib.Path, workload: str, here: pathlib.Path):
    bench = json.loads(bench_path.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {bench_path.name}")
    cell = cells[workload]
    config = json.loads((here / "configs" / f"{cell['config']}.json")
                        .read_text())
    traffic = json.loads((here / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    return bench, cell, config, traffic


def cell_metrics(bench: dict, name: str) -> tuple[list[dict], list[dict]]:
    """The end-to-end and per-layer metrics that cell ``name`` reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def devices(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def peak_bytes(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _limit_ok(value: float, limit: float) -> bool:
    return not math.isnan(value) and value <= limit


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             bench_path: pathlib.Path = ROOT / "BENCHMARK.json",
             here: pathlib.Path = HERE, require_tpu: bool = True) -> dict:
    """Run one cell and return the result line (as a dict)."""
    bench, cell, config, traffic = load_spec(bench_path, workload, here)
    devs = devices(cell["chips"], require_tpu)
    dev = devs[0]
    cache_dir = clock.enable_compile_cache()
    compiles = clock.CompileClock()
    spans = clock.Spans()
    driver = load_module(here / "drivers" / f"{traffic['driver']}.py")

    t0 = time.perf_counter()
    state = driver.setup(config, traffic, seed, spans)
    setup_s = time.perf_counter() - t0
    setup_compiles = compiles.compiles

    summary = None
    if traced:
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        with spans.span("window"):
            result = driver.window(state, seconds, spans)
    finally:
        if traced:
            import jax

            jax.profiler.stop_trace()
    window_compiles = compiles.compiles - setup_compiles
    if traced:
        summary = trace.summarize(trace.load_events(
            trace.find_xplane(str(TRACE_DIR))))
    mem = peak_bytes(devs)

    # the reference runs once the window has closed and the peak is read
    release = getattr(driver, "release", None)
    if release is not None:
        release(state)
    gc.collect()
    t_check = time.perf_counter()
    readings = driver.check(state, result)
    check_s = time.perf_counter() - t_check
    limits = traffic["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in readings
              if limits.get(k) is not None}
    missing = [k for k, v in limits.items() if v is not None
               and k not in checks]
    correct = (not missing and result["failed"] == 0 and result["units"] > 0
               and all(_limit_ok(c["value"], c["limit"])
                       for c in checks.values()))
    for k in missing:
        checks[k] = {"value": None, "limit": limits[k]}

    e2e, layer = cell_metrics(bench, workload)
    metrics = {}
    if traced:
        ctx = {"summary": summary, "result": result, "traffic": traffic,
               "config": config, "window_compiles": window_compiles,
               "device_kind": dev.device_kind, "spans": spans}
        for m in layer:
            value = load_module(here / "metrics" / f"{m['name']}.py").read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(result["e2e"], setup_s=setup_s)
        for m in e2e:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "device_kind": dev.device_kind, "count": len(devs),
              "memory_peak_bytes": mem}
    line = {"correct": bool(correct), "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        line["breakdown"] = {"device_ops": summary.top_ops,
                             "idle_gaps": summary.idle_gaps}
    line["diagnostics"] = {
        "seed": seed, "setup_s": setup_s, "units": result["units"],
        "setup_compiles": setup_compiles, "window_compiles": window_compiles,
        "cache_hits": compiles.hits, "cache_dir": cache_dir,
        "check_s": check_s, "readings": dict(readings)}
    line["checks"] = checks
    return line


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.chdir(ROOT)
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    for k, c in line["checks"].items():
        print(f"check {k} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct = {line['correct']}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0
