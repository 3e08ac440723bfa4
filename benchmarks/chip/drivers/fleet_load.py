"""Fleet under the link's one external load: the ``fleet`` driver, with the
start and the external load of every chunk handed to the reference.

The configuration's ``load`` block states the load that every tenant of
the link runs under (``reference.fleet_load``); the traffic leaves
``constant_load`` null, so ``run_fleet`` builds that load once per fleet,
seeded from the ``env_seed`` of the fleet's request 0.  Set-up, the
window's fleets, arrivals and engine settings are the ``fleet`` driver's.
Set-up first imports the program's link load: a program without one fails
at once, instead of running another deployment's load.

The check holds every fleet to the ``fleet`` driver's guarantees and to
``load_gap``.  Three more controls: ``load_unshared`` plants one chunk's
reported load 0.01 off; ``per_tenant_load`` runs a fleet whose every
request carries a load of its own (the testbed's ``DiurnalTraffic`` under
the request's seed), whose walk steps once per reading; ``load_float32``
puts the reference's load, computed in float32, in the program's place.
"""
from __future__ import annotations

import dataclasses
import sys
import time

import numpy as np

from benchmarks.chip.drivers import fleet as base
from benchmarks.chip.reference import fleet_load as ref_load

CONTROLS = base.CONTROLS + [{"fault": "load_unshared"},
                            {"per_tenant_load": 1}, {"load_float32": 1}]
release = base.release


def setup(config: dict, traffic: dict, seed: int, spans) -> base.State:
    if "load" not in config:
        raise ValueError(f"configuration {config['name']!r} states no "
                         f"load block for the link's one external load")
    # the program's one load per link; a program without it stops here
    from repro.netsim import DiurnalLinkLoad  # noqa: F401

    return base.setup(config, traffic, seed, spans)


def plain(reqs, report) -> tuple[list, list, dict]:
    """``fleet.plain``, its report with every chunk as ``(start, load)``
    rows and the seed of the link's load, request 0's ``env_seed``."""
    requests, sessions, rep = base.plain(reqs, report)
    # one flat list of floats converts faster than a list of pairs
    rep["chunks"] = np.array(
        [x for s in report.sessions for r in s.report.samples
         for x in (r.clock_s, r.ext_load)], np.float64).reshape(-1, 2)
    rep["load_seed"] = int(reqs[0].env_seed)
    return requests, sessions, rep


def window(state: base.State, seconds: float, spans) -> dict:
    done = []
    failed = 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    t_last = t0
    while time.perf_counter() < deadline:
        seed = int(state.fleet_seeds.integers(2**62))
        try:
            done.append(plain(*base.run_one(state, seed, spans)))
        except Exception as e:  # counted and reported; the run is not correct
            print(f"fleet failed: {type(e).__name__}: {e}", file=sys.stderr)
            failed += 1
            continue
        t_last = time.perf_counter()
    n = state.traffic["sessions"]
    return {
        "attempted": n * (len(done) + failed),
        "failed": n * failed,
        "units": len(done),
        "e2e": ({"fleet_sessions_per_s": n * len(done) / (t_last - t0)}
                if done else {}),
        "done": done,
    }


def load_gap(state: base.State, answers) -> float:
    report = answers[2]
    return ref_load.load_gap(report["chunks"], state.config["load"],
                             report["load_seed"])


def readings(state: base.State, know, answers) -> dict[str, float]:
    return {**base.readings(state, know, answers),
            "load_gap": load_gap(state, answers)}


def check(state: base.State, result: dict) -> list[tuple[str, float]]:
    """Readings of every fleet of the window, worst of each number."""
    worst = dict(base.check(state, result))
    if result["done"]:
        worst["load_gap"] = max(load_gap(state, a) for a in result["done"])
    return sorted(worst.items())


def control(state: base.State, result: dict, per_tenant_load: int = 0,
            load_float32: int = 0, fault: str | None = None,
            **kw) -> list[tuple[str, float]]:
    """``fleet.control``, and the three controls of the shared load."""
    if per_tenant_load:
        from repro.core import run_fleet
        from repro.netsim.testbeds import make_traffic

        seed = int(state.fleet_seeds.integers(2**62))
        reqs = [dataclasses.replace(
                    r, traffic=make_traffic(state.config["testbed"],
                                            seed=r.env_seed))
                for r in base.requests(state.config, state.traffic, seed)]
        answers = plain(reqs, run_fleet(state.db, reqs, state.engine))
    elif load_float32:
        reqs, sessions, report = result["done"][0]
        chunks = report["chunks"].copy()
        chunks[:, 1] = ref_load.link_load(state.config["load"],
                                          report["load_seed"], chunks[:, 0],
                                          np.float32)
        answers = (reqs, sessions, dict(report, chunks=chunks))
    elif fault == "load_unshared":
        reqs, sessions, report = result["done"][0]
        report = dict(report, chunks=report["chunks"].copy())
        report["chunks"][0, 1] += 0.01
        answers = (reqs, sessions, report)
    else:
        return base.control(state, result, fault=fault, **kw)
    know = base._reference(state)
    return sorted(readings(state, know, answers).items())
