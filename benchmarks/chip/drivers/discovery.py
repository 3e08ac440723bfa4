"""Offline discovery: back-to-back ``fit_clusters`` over large logs.

Set-up draws ``logs`` feature logs of ``rows`` rows from the seed and fits
one of them once, which compiles every program the window runs.  The
window then fits the logs in turn, each with a fresh fit seed, so no
(log, seed) pair repeats.  ``fit_clusters`` returns host arrays, so each
discovery has finished when the call returns.

The check refits one discovery of the window, drawn from the seed, with
the float64 reference at the sizes of the traffic's ``sweep`` block, and
compares its order, labels, centroids and CH index.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from benchmarks.chip import gen
from benchmarks.chip.reference import discovery as ref

# keyword sets of ``control``: the reference at the two precisions below
# the configuration's float32 at HIGHEST
CONTROLS = [{"precision": "high"}, {"precision": "bf16"}]


class State:
    def __init__(self, config, traffic, seed, logs, fit_seeds, pick):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.logs, self.fit_seeds, self.pick = logs, fit_seeds, pick


def _m_range(traffic) -> range:
    lo, hi = traffic["m_range"]
    return range(lo, hi + 1)


def setup(config: dict, traffic: dict, seed: int, spans) -> State:
    from repro.core.clustering import fit_clusters

    n_logs = traffic["logs"]
    seeds = gen.sub_seeds(seed, n_logs + 3)
    with spans.span("setup.logs"):
        logs = [gen.feature_logs(config, traffic["rows"], s)
                for s in seeds[:n_logs]]
    # fit seeds of the window: one stream, drawn in order
    fit_rng = np.random.default_rng(seeds[n_logs])
    pick_rng = np.random.default_rng(seeds[n_logs + 1])
    with spans.span("setup.warm"):
        fit_clusters(logs[0], m_range=_m_range(traffic), seed=seeds[-1])
    return State(config, traffic, seed, logs,
                 fit_rng, pick_rng.random(traffic["checked_per_run"]))


def window(state: State, seconds: float, spans) -> dict:
    from repro.core.clustering import fit_clusters

    m_range = _m_range(state.traffic)
    done = []  # (log index, fit seed, ClusterModel)
    failed = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    t_last = t0
    while time.perf_counter() < deadline:
        i = len(done) + failed
        li = i % len(state.logs)
        fs = int(state.fit_seeds.integers(2**31))
        try:
            with spans.span("discovery.fit"):
                cm = fit_clusters(state.logs[li], m_range=m_range, seed=fs)
        except Exception as e:  # counted and reported; the run is not correct
            print(f"discovery {i} failed: {type(e).__name__}: {e}",
                  file=sys.stderr)
            failed += 1
            continue
        t_last = time.perf_counter()
        done.append((li, fs, cm))
    n = len(done)
    return {
        "attempted": n + failed,
        "failed": failed,
        "units": n,
        "e2e": {"discovery_s": (t_last - t0) / n} if n else {},
        "done": done,
    }


def check(state: State, result: dict) -> list[tuple[str, float]]:
    """Readings of the sampled discoveries, worst of each number."""
    done = result["done"]
    if not done:
        return [("discoveries", 0.0)]
    worst: dict[str, float] = {}
    for u in state.pick:
        li, fs, cm = done[int(u * len(done))]
        want = ref.fit(state.logs[li], _m_range(state.traffic), fs,
                       state.traffic["sweep"])
        got = ref.compare(cm.m, cm.labels, cm.centroids, cm.ch, want)
        for k, v in got.items():
            worst[k] = max(worst.get(k, 0.0), v)
    return sorted(worst.items())


def control(state: State, result: dict, precision: str
            ) -> list[tuple[str, float]]:
    """The same readings with the reference put in the program's place,
    computed at a lower ``precision`` (see ``reference.discovery``)."""
    worst: dict[str, float] = {}
    for u in state.pick:
        li, fs, _ = result["done"][int(u * len(result["done"]))]
        want = ref.fit(state.logs[li], _m_range(state.traffic), fs,
                       state.traffic["sweep"])
        low = ref.fit(state.logs[li], _m_range(state.traffic), fs,
                      state.traffic["sweep"], precision=precision)
        got = ref.compare(low.m, low.labels, low.centroids, low.ch, want)
        for k, v in got.items():
            worst[k] = max(worst.get(k, 0.0), v)
    return sorted(worst.items())
