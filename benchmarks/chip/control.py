#!/usr/bin/env python3
"""Readings that the limits of a cell are set from, on the chip.

For each of ``--seeds`` seeds: set the cell up, run a short window at the
cell's own size and load, and print the readings of the program's output
against the plain reference (the lower readings).  For the first
``--control-seeds`` of them, also print the readings of the cell's
controls (the upper readings): the reference put in the program's place
at a lower precision, or the program with a path switched on that breaks
a guarantee the configuration states (see each driver's ``control``).
One JSON line per seed; everything runs in this one process.

    python3 benchmarks/chip/control.py --workload discovery.table1-1m \
        --seeds 12 --control-seeds 3 --seconds 2
"""
import argparse
import json
import os
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
# in place of this script's own directory, whose trace.py would shadow the
# standard library's
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(tempfile.gettempdir(), "tpu_logs"))

from benchmarks.chip import clock, gen, harness  # noqa: E402


def readings(workload: str, seeds: list[int], control_seeds: int,
             seconds: float, *, bench_path=harness.ROOT / "BENCHMARK.json",
             here=harness.HERE, require_tpu: bool = True):
    """Yield one dict of readings per seed."""
    bench, cell, config, traffic = harness.load_spec(bench_path, workload,
                                                     here)
    harness.devices(cell["chips"], require_tpu)
    clock.enable_compile_cache()
    driver = harness.load_module(here / "drivers" / f"{traffic['driver']}.py")
    for i, seed in enumerate(seeds):
        spans = clock.Spans()
        state = driver.setup(config, traffic, seed, spans)
        result = driver.window(state, seconds, spans)
        out = {"seed": seed, "units": result["units"],
               "program": dict(driver.check(state, result))}
        if i < control_seeds:
            for kw in driver.CONTROLS:
                t0 = time.perf_counter()
                name = ",".join(f"{k}={v}" for k, v in kw.items())
                try:
                    out[f"control {name}"] = dict(
                        driver.control(state, result, **kw))
                except Exception as e:  # a control that crashes has failed
                    out[f"control {name}"] = {"error": repr(e)}
                out[f"control {name} s"] = time.perf_counter() - t0
        yield out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--first-seed", type=int, default=2**31 + 7)
    args = ap.parse_args()
    seeds = gen.sub_seeds(args.first_seed, args.seeds)
    try:
        for out in readings(args.workload, seeds, args.control_seeds,
                            args.seconds):
            print(json.dumps(out), flush=True)
    except harness.NoChip as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
