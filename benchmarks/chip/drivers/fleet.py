"""Fleet: back-to-back ``run_fleet`` over a queued backlog on one link.

Set-up replays the configuration's history through the simulated testbed,
mines it into an ``OfflineDB`` with ``TransferTuner.fit``, and runs one
warm-up fleet, which compiles the admission-scoring programs.  The history
and the tuner take their seeds from the configuration, so every run serves
from the same knowledge; the run's ``--seed`` draws the fleets.  The window
then runs fleets of ``sessions`` requests back to back, each drawn afresh.

The configuration names the testbed whose link the fleets share.  The
traffic's ``arrivals`` say when requests arrive: absent or null, all are
queued at ``start_clock_s`` of simulated time; else ``{"offered_load"}``,
Poisson arrivals from ``start_clock_s`` at the rate that offers
``offered_load`` of the link's service bound, the lesser of its bandwidth
and its disks, in expected request size (``gen.poisson_arrivals``).  The
testbed's external load is not taken off that bound: where it varies by
the hour, the busy hours offer more than ``offered_load`` of what is left.
``constant_load`` pins each tenant's external load; null leaves the
testbed's diurnal load.

``run_fleet`` takes its defaults except three settings: the configuration's
testbed, the vectorized engine (the threaded default is the test oracle)
and no scoring against the single-tenant optimum, an evaluation oracle
that grid-searches the simulator once per request and is not part of
serving a transfer.

The check holds every fleet of the window to the guarantees of
``reference.fleet``, with the knowledge as set-up left it.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from benchmarks.chip import clock, gen
from benchmarks.chip.reference import fleet as ref

# keyword sets of ``control``: two paths of the program that break a
# guarantee, then faults planted in what a window's fleet reported
CONTROLS = [{"kills": 8}, {"cap_shift": 1}, {"fault": "cap_reported_low"},
            {"fault": "over_link"}, {"fault": "params_shifted"},
            {"fault": "admitted_early"}, {"fault": "queue_jumped"}]
# entropy word of the arrival process's own stream, beside the run seed
ARRIVALS_STREAM = 0xA441


class State:
    def __init__(self, config, traffic, db, knowledge, fleet_seeds,
                 engine=None):
        self.config, self.traffic = config, traffic
        self.db, self.knowledge = db, knowledge
        self.fleet_seeds = fleet_seeds
        self.engine = engine  # the ``EngineConfig`` of the window's fleets


def arrivals(config: dict, traffic: dict, n: int, seed: int) -> np.ndarray:
    """Arrival times (simulated s) of one fleet's ``n`` requests."""
    t0 = traffic["start_clock_s"]
    a = traffic.get("arrivals")
    if a is None:
        return np.full(n, t0)
    link = config["link"]
    rate = (a["offered_load"] * min(link["bandwidth_mbps"], link["disk_mbps"])
            / (8.0 * gen.mean_request_mb(config, traffic["classes"])))
    return t0 + gen.poisson_arrivals(
        n, rate, np.random.SeedSequence([seed, ARRIVALS_STREAM]))


def requests(config: dict, traffic: dict, seed: int):
    """One fleet's requests, drawn from ``seed``."""
    from repro.core import FleetRequest
    from repro.netsim.workload import Dataset

    n = traffic["sessions"]
    ds = gen.datasets(config, traffic["classes"], n, seed)
    env_seeds = np.random.default_rng(seed ^ 0x5EED).integers(2**31, size=n)
    at = arrivals(config, traffic, n, seed)
    return [FleetRequest(
        dataset=Dataset(f"{fc}-{i}", fc, avg, nf),
        env_seed=int(es),
        start_clock_s=float(t),
        constant_load=traffic["constant_load"],
    ) for i, ((fc, avg, nf), es, t) in enumerate(zip(ds, env_seeds, at))]


def engine_config(config: dict, **extra):
    from repro.core import EngineConfig

    return EngineConfig(engine="vectorized", testbed=config["testbed"],
                        score_vs_single=False, **extra)


def knowledge(db) -> dict:
    """The knowledge the window serves from, as plain numbers: the routing
    centroids and each cluster's surfaces (load tag, knots, grid)."""
    return {
        "centroids": np.array(db.cluster_model.centroids, np.float64),
        "clusters": [[{"load": float(s.load_intensity),
                       "gp": np.array(s.surface.gp, np.float64),
                       "gcc": np.array(s.surface.gcc, np.float64),
                       "gpp": np.array(s.surface.gpp, np.float64),
                       "grid": np.array(s.surface.grid, np.float64)}
                      for s in c.surfaces] for c in db.clusters],
    }


def setup(config: dict, traffic: dict, seed: int, spans) -> State:
    from repro.core import TransferTuner, TunerConfig, run_fleet
    from repro.netsim import generate_history, make_testbed

    h = config["history"]
    s_env, s_hist, s_tuner = gen.sub_seeds(h["seed"], 3)
    s_warm, s_fleets = gen.sub_seeds(seed, 2)
    with spans.span("setup.history"):
        hist = generate_history(
            make_testbed(config["testbed"], seed=s_env % 2**31),
            days=h["days"], transfers_per_day=h["transfers_per_day"],
            seed=s_hist)
    with spans.span("setup.fit"):
        db = TransferTuner(TunerConfig(seed=s_tuner % 2**31)).fit(hist).db
    engine = engine_config(config)
    with spans.span("setup.warm"):
        run_fleet(db, requests(config, traffic, s_warm), engine)
    return State(config, traffic, db, knowledge(db),
                 np.random.default_rng(s_fleets), engine)


def run_one(state: State, seed: int, spans, engine=None):
    from repro.core import run_fleet

    reqs = requests(state.config, state.traffic, seed)
    with spans.span("fleet.run_fleet"):
        report = run_fleet(state.db, reqs, engine or state.engine)
    return reqs, report


def window(state: State, seconds: float, spans) -> dict:
    # each fleet's answers, as plain numbers: the reports themselves are
    # let go, so the window's heap, and the collector's work, stays flat
    done = []
    failed = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    t_last = t0
    while time.perf_counter() < deadline:
        seed = int(state.fleet_seeds.integers(2**62))
        try:
            done.append(plain(*run_one(state, seed, spans)))
        except Exception as e:  # counted and reported; the run is not correct
            print(f"fleet failed: {type(e).__name__}: {e}", file=sys.stderr)
            failed += 1
            continue
        t_last = time.perf_counter()
    n = state.traffic["sessions"]
    sessions = n * len(done)
    return {
        "attempted": n * (len(done) + failed),
        "failed": n * failed,
        "units": len(done),
        "e2e": ({"fleet_sessions_per_s": sessions / (t_last - t0)}
                if done else {}),
        "done": done,
    }


def plain(reqs, report) -> tuple[list, list, dict]:
    """The requests and the report as the plain numbers the reference reads."""
    return (
        [{"avg_file_mb": r.dataset.avg_file_mb, "n_files": r.dataset.n_files,
          "arrival_s": r.start_clock_s} for r in reqs],
        [{"request": s.request_index, "attempt": s.attempt,
          "admit_s": s.admit_s, "end_s": s.end_s,
          "moved_mb": s.report.moved_mb,
          "achieved_mbps": s.report.achieved_mbps,
          "interrupted": s.report.interrupted,
          "params": (None if s.report.params is None
                     else tuple(int(v) for v in s.report.params.as_tuple()))}
         for s in report.sessions],
        {"goodput_mbps": report.goodput_mbps,
         "admitted_concurrency": report.admitted_concurrency})


def _reference(state: State) -> ref.Knowledge:
    return ref.Knowledge(state.knowledge, state.config["param_domain"])


def readings(state: State, know: ref.Knowledge, answers) -> dict[str, float]:
    """One fleet's answers held to ``reference.fleet``."""
    return ref.compare(*answers, state.config["link"],
                       state.config["admission_overcommit"], know)


def release(state: State) -> None:
    state.db = None


def check(state: State, result: dict) -> list[tuple[str, float]]:
    """Readings of every fleet of the window, worst of each number."""
    if not result["done"]:
        return [("fleets", 0.0)]
    know = _reference(state)
    worst: dict[str, float] = {}
    for answers in result["done"]:
        for k, v in readings(state, know, answers).items():
            worst[k] = max(worst.get(k, 0.0), v)
    return sorted(worst.items())


def queued_pair(arrival_s: list[float], cap: int) -> tuple[int, int]:
    """The requests of the first and the last queued admission: in arrival
    order (ties by index), past the first ``cap``."""
    order = sorted(range(len(arrival_s)), key=lambda i: (arrival_s[i], i))
    if len(order) - cap < 2:
        raise ValueError(
            f"no queue to jump: {len(order)} requests under a cap of {cap} "
            f"leave {max(len(order) - cap, 0)} queued, and a swap needs two")
    return order[cap], order[-1]


def _planted(answers, fault: str, bandwidth: float, max_cc: int):
    reqs, sessions, report = answers
    sessions = [dict(s) for s in sessions]
    report = dict(report)
    first = {s["request"]: s for s in sessions if s["attempt"] == 0}
    if fault == "cap_reported_low":
        report["admitted_concurrency"] -= 1
    elif fault == "over_link":
        sessions[0]["achieved_mbps"] = 1.01 * bandwidth
    elif fault == "params_shifted":
        # every session run a step of concurrency off its surface's optimum
        for s in sessions:
            if s["params"] is not None:
                cc, p, pp = s["params"]
                s["params"] = (cc + 1 if cc < max_cc else cc - 1, p, pp)
    elif fault == "admitted_early":
        # the last request to arrive admitted a second before it arrived
        last = max(range(len(reqs)), key=lambda i: (reqs[i]["arrival_s"], i))
        first[last]["admit_s"] = reqs[last]["arrival_s"] - 1.0
    elif fault == "queue_jumped":
        # the first and the last queued request admitted in each other's turn
        a, b = queued_pair([r["arrival_s"] for r in reqs],
                           report["admitted_concurrency"])
        first[a]["admit_s"], first[b]["admit_s"] = (first[b]["admit_s"],
                                                    first[a]["admit_s"])
    else:
        raise ValueError(f"unknown fault {fault!r}")
    return reqs, sessions, report


def control(state: State, result: dict, kills: int = 0, cap_shift: int = 0,
            fault: str | None = None) -> list[tuple[str, float]]:
    """Readings of one fleet of the cell's size that breaks a guarantee.

    ``kills``: fault injection switched on with recovery off, so every
    session in flight dies at each of ``kills`` instants.  ``cap_shift``:
    the admission cap set ``cap_shift`` above the one the window's first
    fleet was given.
    ``fault``: a fault planted in the window's first fleet's answers.
    """
    from repro.netsim import FaultSchedule, TenantKill

    first = result["done"][0]
    if fault is not None:
        answers = _planted(first, fault, state.config["link"]["bandwidth_mbps"],
                           state.config["param_domain"]["cc"])
    else:
        extra = {}
        seed = int(state.fleet_seeds.integers(2**62))
        if kills:
            # the k-th kill 30 (k + 1) s after the k-th arrival
            at = arrivals(state.config, state.traffic,
                          state.traffic["sessions"], seed)
            extra["faults"] = FaultSchedule(tuple(
                TenantKill(at_s=float(at[min(k, len(at) - 1)])
                           + 30.0 * (k + 1), tenant_id=None)
                for k in range(kills)))
        if cap_shift:
            extra["max_concurrent"] = (first[2]["admitted_concurrency"]
                                       + cap_shift)
        answers = plain(*run_one(state, seed, clock.Spans(),
                                 engine_config(state.config, **extra)))
    return sorted(readings(state, _reference(state), answers).items())
