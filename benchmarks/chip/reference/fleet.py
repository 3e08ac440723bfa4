"""Plain check of a fleet run against the guarantees its configuration states.

Reads only the requests the benchmark generated, the report the program
returned and the frozen knowledge the window served from (each as plain
numbers), and imports nothing of the program:

* ``lost_requests``   -- requests with no completed, uninterrupted session;
* ``shortfall_mb``    -- largest amount by which a request was delivered
  short of its dataset (``avg_file_mb * n_files``);
* ``link_excess``     -- largest session rate above the link's bandwidth, as
  a share of the bandwidth;
* ``over_cap``        -- most sessions running at once, less the admission
  cap the run reported;
* ``goodput_rel_gap`` -- reported goodput against the delivered megabits
  over the makespan (first admission to last finish);
* ``cap_gap``         -- the reported admission cap against the cap that
  the knowledge gives: each request routed to its nearest centroid, its
  demand the best value of that cluster's median-load surface on the
  integer lattice, the cap ``overcommit * bandwidth / median demand``;
* ``param_gap``       -- largest shortfall, as a share of the surface's
  best, of a session's parameters from the lattice optimum of the surface
  of its cluster that they come closest to optimising;
* ``early_admits``    -- sessions admitted before their request arrived;
* ``queue_gap``       -- largest distance of a request's admission from the
  one that first-come-first-served, work-conserving admission under the
  reported cap gives (:func:`fifo_admissions`).

The knowledge is each cluster's centroid and its throughput surfaces, each
a natural cubic spline through a grid of throughputs over parallelism,
concurrency and pipelining knots, the end pieces extended past the knots.
"""
from __future__ import annotations

import heapq
import math

import numpy as np

# relative room of the admission cap for the program's float32 scoring: a
# median demand within this share of a cap's edge may round to either side
CAP_ROOM = 1e-6


def _max_concurrent(intervals: list[tuple[float, float]]) -> int:
    """Most intervals open at once; one that ends at t is closed before one
    that starts at t is opened."""
    ev = sorted([(e, 0) for _, e in intervals] + [(s, 1) for s, _ in intervals])
    live = best = 0
    for _, kind in ev:
        live += 1 if kind else -1
        best = max(best, live)
    return best


def fifo_admissions(arrival_s: list[float], end_s: dict[int, float],
                    cap: int) -> dict[int, float]:
    """Admission time of each request in ``end_s`` under first-come-first-
    served admission that never idles a free slot below ``cap``.

    In arrival order (ties by index), the first ``cap`` requests are
    admitted when they arrive; each later one when it arrives or when the
    earliest end still holding a slot frees it, whichever is later; then
    its own end holds the slot.  ``end_s`` maps a request to the end of its
    first session.  Recovery re-admissions are left out: they queue behind
    every first request, so they move no first admission.  A request with
    no session is left out too (``lost_requests`` counts it).
    """
    order = sorted(end_s, key=lambda i: (arrival_s[i], i))
    held: list[float] = []
    out: dict[int, float] = {}
    for k, i in enumerate(order):
        out[i] = (arrival_s[i] if k < cap
                  else max(arrival_s[i], heapq.heappop(held)))
        heapq.heappush(held, end_s[i])
    return out


def admission(requests: list[dict], sessions: list[dict],
              cap: int) -> dict[str, float]:
    """``early_admits`` and ``queue_gap`` of a fleet whose reported cap is
    ``cap`` (the arguments as :func:`compare` takes them)."""
    arrival = [r["arrival_s"] for r in requests]
    early = sum(1 for s in sessions if s["admit_s"] < arrival[s["request"]])
    first = {s["request"]: s for s in sessions if s["attempt"] == 0}
    fifo = fifo_admissions(arrival, {i: s["end_s"] for i, s in first.items()},
                           cap)
    gap = max((abs(first[i]["admit_s"] - t) for i, t in fifo.items()),
              default=0.0)
    return {"early_admits": float(early), "queue_gap": gap}


def spline_operator(x, q) -> np.ndarray:
    """``(len(q), len(x))`` matrix ``W``: ``W @ y`` is the natural cubic
    spline through ``(x, y)`` at the points ``q``.

    One knot gives a constant, two a line.  From three knots on, the second
    derivatives ``M`` solve the natural end conditions, and each piece is
    ``M_i (x_{i+1} - q)^3 / 6h + M_{i+1} (q - x_i)^3 / 6h`` plus the line
    that meets the knot values; the first and last pieces extend past the
    knots.
    """
    x = np.asarray(x, np.float64)
    q = np.asarray(q, np.float64)
    n = len(x)
    if n == 1:
        return np.ones((len(q), 1))
    # piece of each query point, clipped to the first and last
    i = np.clip(np.searchsorted(x, q, side="right") - 1, 0, n - 2)
    h = x[i + 1] - x[i]
    a, b = (x[i + 1] - q) / h, (q - x[i]) / h  # weights of the two knots
    W = np.zeros((len(q), n))
    rows = np.arange(len(q))
    W[rows, i] += a
    W[rows, i + 1] += b
    if n == 2:
        return W
    # second derivatives at the knots, per unit knot value: A M = R y
    hs = np.diff(x)
    A = np.zeros((n, n))
    R = np.zeros((n, n))
    A[0, 0] = A[-1, -1] = 1.0
    for k in range(1, n - 1):
        A[k, k - 1], A[k, k], A[k, k + 1] = hs[k - 1], 2 * (hs[k - 1] + hs[k]), hs[k]
        R[k, k - 1] = 6.0 / hs[k - 1]
        R[k, k] = -6.0 / hs[k - 1] - 6.0 / hs[k]
        R[k, k + 1] = 6.0 / hs[k]
    Mop = np.linalg.solve(A, R)  # (n, n): knot values -> second derivatives
    ca = (a ** 3 - a) * h * h / 6.0
    cb = (b ** 3 - b) * h * h / 6.0
    return W + ca[:, None] * Mop[i] + cb[:, None] * Mop[i + 1]


def lattice(surface: dict, domain: dict) -> np.ndarray:
    """A surface's values at every integer point: ``[p-1, cc-1, pp-1]``."""
    Wp = spline_operator(surface["gp"], np.arange(1, domain["p"] + 1))
    Wc = spline_operator(surface["gcc"], np.arange(1, domain["cc"] + 1))
    Wq = spline_operator(surface["gpp"], np.arange(1, domain["pp"] + 1))
    return np.einsum("ai,bj,ck,ijk->abc", Wp, Wc, Wq,
                     np.asarray(surface["grid"], np.float64))


class Knowledge:
    """The frozen knowledge, evaluated once: routing and lattice values."""

    def __init__(self, knowledge: dict, domain: dict):
        self.centroids = np.asarray(knowledge["centroids"], np.float64)
        # per cluster, its surfaces by ascending load: (S, P, C, Q)
        self.values = [np.stack([lattice(s, domain) for s in
                                 sorted(c, key=lambda s: s["load"])])
                       for c in knowledge["clusters"]]
        self.best = [v.reshape(len(v), -1).max(1) for v in self.values]

    def route(self, features: np.ndarray) -> np.ndarray:
        """Nearest centroid of each ``(n, d)`` feature row."""
        d2 = ((features[:, None, :] - self.centroids[None]) ** 2).sum(-1)
        return d2.argmin(1)

    def demand(self, k: int) -> float:
        """Best value of cluster ``k``'s median-load surface."""
        return float(self.best[k][len(self.best[k]) // 2])

    def param_gap(self, k: int, cc: int, p: int, pp: int) -> float:
        """Shortfall of ``(cc, p, pp)`` from the best of the closest of
        cluster ``k``'s surfaces, as a share of that best."""
        v = self.values[k][:, p - 1, cc - 1, pp - 1]
        best = self.best[k]
        return float(((best - v) / np.maximum(np.abs(best), 1e-12)).min())


def features(link: dict, requests: list[dict]) -> np.ndarray:
    """Cluster features of each request: log10 of the link's bandwidth and
    RTT and of the dataset's mean file size and file count."""
    return np.array([[math.log10(link["bandwidth_mbps"]),
                      math.log10(max(link["rtt_s"], 1e-5)),
                      math.log10(r["avg_file_mb"]), math.log10(r["n_files"])]
                     for r in requests])


def admission_cap(demands: np.ndarray, link: dict, overcommit: float,
                  room: float = 0.0) -> set[int]:
    """The caps the median demand allows, with ``room`` on either side."""
    n = len(demands)
    med = float(np.median(demands))
    if med <= 0.0:
        return {n}
    x = overcommit * link["bandwidth_mbps"] / med
    return {max(1, min(int(x * f), n)) for f in (1.0 - room, 1.0, 1.0 + room)}


def compare(requests: list[dict], sessions: list[dict], report: dict,
            link: dict, overcommit: float, know: Knowledge) -> dict[str, float]:
    """``requests``: ``{"avg_file_mb", "n_files", "arrival_s"}`` per
    request, in order.  ``sessions``: ``{"request", "attempt", "admit_s",
    "end_s", "moved_mb", "achieved_mbps", "interrupted", "params"}`` per
    session attempt (``attempt`` 0 for the first), with ``params`` as
    ``(cc, p, pp)`` or ``None``.  ``report``:
    ``{"goodput_mbps", "admitted_concurrency"}``."""
    bandwidth = link["bandwidth_mbps"]
    served = {s["request"] for s in sessions if not s["interrupted"]}
    lost = sum(1 for i in range(len(requests)) if i not in served)
    delivered: dict[int, float] = {}
    for s in sessions:
        delivered[s["request"]] = delivered.get(s["request"], 0.0) + s["moved_mb"]
    shortfall = max((r["avg_file_mb"] * r["n_files"] - delivered.get(i, 0.0)
                     for i, r in enumerate(requests)), default=0.0)
    excess = max((s["achieved_mbps"] / bandwidth - 1.0 for s in sessions),
                 default=0.0)
    conc = _max_concurrent([(s["admit_s"], s["end_s"]) for s in sessions])
    if sessions:
        span = (max(s["end_s"] for s in sessions)
                - min(s["admit_s"] for s in sessions))
        want = math.fsum(s["moved_mb"] for s in sessions) * 8.0 / span
        gap = abs(report["goodput_mbps"] - want) / want
    else:
        gap = math.inf

    cluster = know.route(features(link, requests))
    demands = np.array([know.demand(k) for k in cluster])
    caps = admission_cap(demands, link, overcommit, CAP_ROOM)
    got = report["admitted_concurrency"]
    cap_gap = 0 if got in caps else min(abs(got - c) for c in caps)
    param_gap = max((know.param_gap(int(cluster[s["request"]]), *s["params"])
                     for s in sessions if s["params"] is not None),
                    default=0.0)
    return {
        "lost_requests": float(lost),
        "shortfall_mb": max(shortfall, 0.0),
        "link_excess": max(excess, 0.0),
        "over_cap": float(max(conc - got, 0)),
        "goodput_rel_gap": gap,
        "cap_gap": float(cap_gap),
        "param_gap": max(param_gap, 0.0),
        **admission(requests, sessions, got),
    }
