"""Benchmark harness — one module per paper table/figure plus the
beyond-paper checkpoint-tuning, kernel, and fleet-scale benchmarks.

Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--only fig5,fleet] [--smoke]
                                            [--out bench.csv]

``--smoke`` shrinks every module's iteration counts so the whole harness
finishes in a couple of minutes on a CI runner; ``--out`` tees the CSV rows
to a file (uploaded as an artifact by the bench-smoke CI job).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

MODULES = [
    ("fig3b", "benchmarks.fig3b_surfaces"),
    ("fig5", "benchmarks.fig5_throughput"),
    ("fig6", "benchmarks.fig6_accuracy"),
    ("fig7", "benchmarks.fig7_periodic"),
    ("convergence", "benchmarks.tab_convergence"),
    ("ckpt", "benchmarks.ckpt_tuning"),
    ("kernels", "benchmarks.kernels_bench"),
    ("fleet", "benchmarks.fleet_scale"),
    ("shard", "benchmarks.fleet_shard"),
    ("refresh", "benchmarks.refresh_drift"),
    ("offline", "benchmarks.offline_scale"),
    ("faults", "benchmarks.fault_recovery"),
    ("knowledge", "benchmarks.knowledge_qps"),
]


class _Tee:
    """Mirror writes to several streams (stdout + the --out CSV file)."""

    def __init__(self, *streams):
        self.streams = streams

    def write(self, s):
        for st in self.streams:
            st.write(s)

    def flush(self):
        for st in self.streams:
            st.flush()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: "
                         + ",".join(k for k, _ in MODULES))
    ap.add_argument("--smoke", action="store_true",
                    help="shrink iteration counts for CI smoke runs")
    ap.add_argument("--out", default=None,
                    help="also write the CSV rows to this file")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - {k for k, _ in MODULES}
        if unknown:
            # a silent no-op harness would look green in CI
            ap.error(f"unknown --only keys: {','.join(sorted(unknown))}")

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    out_file = open(args.out, "w") if args.out else None
    prev_stdout = sys.stdout
    if out_file is not None:
        sys.stdout = _Tee(prev_stdout, out_file)

    failures = 0
    try:
        for key, modname in MODULES:
            if only and key not in only:
                continue
            t0 = time.perf_counter()
            try:
                mod = __import__(modname, fromlist=["main"])
                mod.main(smoke=args.smoke)
                wall = (time.perf_counter() - t0) * 1e6
                print(f"bench_{key}_wall,{wall:.0f},ok")
            except Exception as e:
                failures += 1
                # the wall row must survive failures so per-PR CSV diffs
                # always show how far (and how long) each module got
                wall = (time.perf_counter() - t0) * 1e6
                print(f"bench_{key}_wall,{wall:.0f},FAILED {e}")
                traceback.print_exc()
    finally:
        if out_file is not None:
            sys.stdout = prev_stdout
            out_file.close()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
