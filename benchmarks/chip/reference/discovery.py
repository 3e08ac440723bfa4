"""Plain reference of batched discovery: k-means++ seeding, mini-batch
k-means over every model order, exact Lloyd refinement, a final labelling
pass and Calinski-Harabasz order selection, in numpy.

It follows the algorithm the program documents for
``fit_clusters_batched`` step for step and draws the same random numbers
from the same seed (the subsample, the k-means++ seeds and the mini-batch
indices), so on the same log and seed it reaches the same partition unless
rounding moves a point across a boundary.  ``precision="float64"`` is the
reference.  The two lower precisions are controls, computed in float32 as
the TPU's matrix unit computes them: ``"high"`` splits each product's
operands into two bfloat16 halves and drops the low-times-low term (the
three-pass ``Precision.HIGH``); ``"bf16"`` rounds the operands to bfloat16
once (the one-pass default precision).

Imports nothing of the program.
"""
from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor

import numpy as np

SENTINEL = 1.0e6
CHUNK = 65536


@dataclasses.dataclass
class Fit:
    m: int
    labels: np.ndarray
    centroids: np.ndarray
    ch: float


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    r = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))
         ) & np.uint32(0xFFFF0000)
    return r.view(np.float32)


class _Arith:
    """Array type and matrix product of one precision."""

    def __init__(self, precision: str):
        if precision not in ("float64", "high", "bf16"):
            raise ValueError(f"unknown precision {precision!r}")
        self.precision = precision
        self.dtype = np.float64 if precision == "float64" else np.float32

    def cast(self, x):
        return np.asarray(x, self.dtype)

    def operand(self, x):
        """A product operand as the matrix unit sees it."""
        if self.precision == "float64":
            return x
        hi = _bf16(x)
        return hi if self.precision == "bf16" else hi + _bf16(x - hi)

    def dot(self, a, b):
        if self.precision != "high":
            return self.operand(a) @ self.operand(b)
        a_hi, b_hi = _bf16(a), _bf16(b)
        a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)
        return a_hi @ b_hi + (a_hi @ b_lo + a_lo @ b_hi)


def kmeans_pp_init(X: np.ndarray, m: int, rng: np.random.Generator):
    """K-means++ seeding (Arthur & Vassilvitskii 2007), float64."""
    n = X.shape[0]
    centers = [X[rng.integers(n)]]
    for _ in range(1, m):
        d2 = np.min(((X[:, None, :] - np.asarray(centers)[None]) ** 2
                     ).sum(-1), axis=1)
        total = d2.sum()
        if not np.isfinite(total) or total <= 1e-12:
            centers.append(X[rng.integers(n)])
            continue
        centers.append(X[rng.choice(n, p=d2 / total)])
    return np.asarray(centers)


def _assign(ar: _Arith, x, C, K: int, M: int):
    """(B, d) points vs (K, M, d) centroids -> (B, K) nearest labels."""
    Cf = C.reshape(K * M, -1)
    c2 = (Cf * Cf).sum(-1)[None, :]
    if ar.precision == "float64":
        # |x|^2 is the same for every centroid of a row, so the argmin
        # needs only c2 - 2 x.c: one product of [x, 1] with [-2 C; c2]
        xa = np.concatenate([x, np.ones((x.shape[0], 1))], axis=1)
        d2 = xa @ np.concatenate([-2.0 * Cf.T, c2], axis=0)
    else:
        x2 = (x * x).sum(-1)[:, None]
        d2 = x2 - 2.0 * ar.dot(x, Cf.T) + c2
    return d2.reshape(-1, K, M).argmin(-1)


def _cluster_sums(ar: _Arith, x, lab, K: int, M: int):
    """Per-(order, cluster) coordinate sums and counts of one labelling."""
    d = x.shape[1]
    flat = (lab + M * np.arange(K)[None, :]).ravel()
    cnt = np.bincount(flat, minlength=K * M).astype(ar.dtype)
    # the one-hot operand is exact at every precision; the points are not
    xs = ar.operand(x)
    sums = np.stack([
        np.bincount(flat, weights=np.repeat(xs[:, j], K), minlength=K * M)
        for j in range(d)], axis=1).astype(ar.dtype)
    return sums.reshape(K, M, d), cnt.reshape(K, M)


def _ch(n, sq_total, overall, cnt, sums):
    cents = sums / np.maximum(cnt, 1.0)[:, None]
    occ = cnt > 0
    m_eff = int(occ.sum())
    if m_eff < 2 or m_eff >= n:
        return -np.inf, cents
    within = max(sq_total - float((cnt[occ] * (cents[occ] ** 2).sum(-1)
                                   ).sum()), 0.0)
    between = float((cnt[occ] * ((cents[occ] - overall[None]) ** 2).sum(-1)
                     ).sum())
    if within <= 1e-12 * max(sq_total, 1.0):
        return np.inf, cents
    return float((between / (m_eff - 1)) / (within / (n - m_eff))), cents


def fit(X: np.ndarray, m_range, seed: int, sweep: dict, *,
        precision: str = "float64", threads: int = 8) -> Fit:
    """Fit every order of ``m_range`` and keep the one with the largest CH.

    ``sweep`` holds the algorithm's sizes, as the traffic file states them:
    ``batch`` (mini-batch rows), ``minibatch_iters``, ``refine_iters`` and
    ``init_subsample`` (rows the k-means++ seeding draws from).
    """
    batch_size, minibatch_iters = sweep["batch"], sweep["minibatch_iters"]
    refine_iters, init_subsample = sweep["refine_iters"], sweep["init_subsample"]
    ar = _Arith(precision)
    X = np.ascontiguousarray(np.asarray(X, np.float64))
    n, d = X.shape
    ms = [int(m) for m in m_range if 2 <= m < n]
    rng = np.random.default_rng(seed)
    sub = (X if n <= init_subsample
           else X[rng.choice(n, init_subsample, replace=False)])
    K, M = len(ms), max(ms)
    C0 = np.full((K, M, d), SENTINEL)
    for i, m in enumerate(ms):
        C0[i, :m] = kmeans_pp_init(sub, m, rng)
    B = min(batch_size, n)
    batches = rng.integers(0, n, size=(minibatch_iters, B))

    Xa = ar.cast(X)
    C = ar.cast(C0)
    counts = np.zeros((K, M), ar.dtype)
    for idx in batches:
        xb = Xa[idx]
        sums, cnt = _cluster_sums(ar, xb, _assign(ar, xb, C, K, M), K, M)
        counts = counts + cnt
        lr = np.where(cnt > 0, cnt / np.maximum(counts, 1.0), 0.0)
        tgt = sums / np.maximum(cnt, 1.0)[..., None]
        C = (C + lr[..., None] * (tgt - C)).astype(ar.dtype)

    def chunk_pass(C, i):
        xc = Xa[i:i + CHUNK]
        lab = _assign(ar, xc, C, K, M)
        return lab, *_cluster_sums(ar, xc, lab, K, M)

    def data_pass(pool, C):
        # chunks run in threads (numpy releases the interpreter lock in
        # its array loops) and are reduced in chunk order
        parts = list(pool.map(lambda i: chunk_pass(C, i),
                              range(0, n, CHUNK)))
        sums = np.zeros((K, M, d), ar.dtype)
        cnt = np.zeros((K, M), ar.dtype)
        for _, s, c in parts:
            sums, cnt = sums + s, cnt + c
        return sums, cnt, np.concatenate([p[0] for p in parts])

    with ThreadPoolExecutor(max_workers=threads) as pool:
        for _ in range(max(refine_iters, 0)):
            sums, cnt, _ = data_pass(pool, C)
            new = sums / np.maximum(cnt, 1.0)[..., None]
            C = np.where(cnt[..., None] > 0, new, C).astype(ar.dtype)
        sums, cnt, labs = data_pass(pool, C)

    C = np.asarray(C, np.float64)
    sums = np.asarray(sums, np.float64)
    cnt = np.asarray(cnt, np.float64)
    sq_total = float((X * X).sum())
    overall = X.mean(0)
    best, best_i = None, -1
    for i, m in enumerate(ms):
        score, cents = _ch(n, sq_total, overall, cnt[i, :m], sums[i, :m])
        cents = np.where((cnt[i, :m] > 0)[:, None], cents, C[i, :m])
        if best is None or score > best[0]:
            best, best_i = (score, cents, m), i
    score, cents, m = best
    return Fit(m, np.asarray(labs[:, best_i], np.int64), cents, score)


def compare(got_m: int, got_labels, got_centroids, got_ch: float,
            ref: Fit) -> dict[str, float]:
    """The numbers compared: order, label, centroid and CH gaps to ``ref``."""
    got_labels = np.asarray(got_labels).ravel()
    if got_m != ref.m or got_labels.shape != ref.labels.shape:
        return {"order_differs": 1.0, "label_mismatch_share": 1.0,
                "centroid_rel_gap": float("inf"),
                "ch_rel_gap": float("inf")}
    scale = float(np.abs(ref.centroids).max())
    gap = float(np.abs(np.asarray(got_centroids, np.float64)
                       - ref.centroids).max()) / max(scale, 1e-12)
    return {"order_differs": 0.0,
            "label_mismatch_share": float((got_labels != ref.labels).mean()),
            "centroid_rel_gap": gap,
            "ch_rel_gap": abs(float(got_ch) - ref.ch) / abs(ref.ch)}
