"""Input generators of the benchmark, driven by a configuration's data.

Every input is drawn from the run's ``--seed``; the program receives only
the generated inputs.  The samplers follow the paper's Sec. 4 file classes
(log-uniform mean file size, uniform file count inside each class) and the
Table-1 link facts that the configuration file lists.
"""
from __future__ import annotations

import numpy as np


def sub_seeds(seed: int, n: int) -> list[int]:
    """``n`` independent 63-bit seeds derived from one run seed."""
    ss = np.random.SeedSequence(int(seed))
    return [int(s) for s in ss.generate_state(n, np.uint64) >> np.uint64(1)]


def feature_logs(config: dict, n: int, seed: int) -> np.ndarray:
    """Clustering features of ``n`` log rows over the configuration's testbeds.

    Each row picks a testbed and a file class uniformly, then a log-uniform
    mean file size and a uniform file count inside the class; the features
    are the log10 of bandwidth, RTT, mean file size and file count.
    Returns an ``(n, 4)`` float64 array.
    """
    beds = list(config["testbeds"].values())
    classes = list(config["file_classes"].values())
    rng = np.random.default_rng(seed)
    bw = np.array([b["bandwidth_mbps"] for b in beds])
    rtt = np.array([b["rtt_s"] for b in beds])
    net = rng.integers(0, len(beds), n)
    lo = np.array([c["avg_file_mb"][0] for c in classes])
    hi = np.array([c["avg_file_mb"][1] for c in classes])
    n_lo = np.array([c["n_files"][0] for c in classes])
    n_hi = np.array([c["n_files"][1] for c in classes])
    fc = rng.integers(0, len(classes), n)
    avg = np.exp(rng.uniform(np.log(lo[fc]), np.log(hi[fc])))
    n_files = rng.integers(n_lo[fc], n_hi[fc] + 1)
    return np.stack([
        np.log10(bw[net]),
        np.log10(np.maximum(rtt[net], 1e-5)),
        np.log10(avg),
        np.log10(n_files),
    ], axis=1)


def datasets(config: dict, classes: list[str], n: int,
             seed: int) -> list[tuple[str, float, int]]:
    """``n`` transfer datasets ``(file_class, avg_file_mb, n_files)``.

    Classes come in equal shares (request ``i`` takes ``classes[i % len]``),
    so every seed asks for the same mix of small, medium and large work.
    """
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        fc = classes[i % len(classes)]
        spec = config["file_classes"][fc]
        lo, hi = spec["avg_file_mb"]
        avg = float(np.exp(rng.uniform(np.log(lo), np.log(hi))))
        nf = int(rng.integers(spec["n_files"][0], spec["n_files"][1] + 1))
        out.append((fc, avg, nf))
    return out


def mean_request_mb(config: dict, classes: list[str]) -> float:
    """Expected size of one request (MB) under :func:`datasets`: the mean
    over the classes, each the log-uniform mean of its mean file size times
    the uniform mean of its file count."""
    out = 0.0
    for fc in classes:
        spec = config["file_classes"][fc]
        lo, hi = spec["avg_file_mb"]
        avg = lo if hi == lo else (hi - lo) / np.log(hi / lo)
        out += avg * 0.5 * (spec["n_files"][0] + spec["n_files"][1])
    return float(out / len(classes))


def poisson_arrivals(n: int, rate: float, seed) -> np.ndarray:
    """``n`` arrival offsets (s, ascending, from 0) of a Poisson process of
    ``rate`` arrivals per second.  ``seed`` is anything
    ``np.random.default_rng`` takes."""
    return np.cumsum(np.random.default_rng(seed).exponential(1.0 / rate, n))
