"""Test support of the ``fleet`` driver: its tiny size, the faults planted
under its timed path, and the trace recorded on the chip that its traced
runs read in place of the CPU's."""
from __future__ import annotations

import dataclasses

RECORDED = "fleet.xplane.pb.gz"
FAULTS = ["state_unchanged", "half_batch", "answer_altered", "cap_shifted",
          "cap_reported_low", "over_link", "params_shifted",
          "admitted_early", "queue_jumped"]


def shrink(config: dict, traffic: dict) -> tuple[dict, dict]:
    """24 sessions a fleet, knowledge mined from one day of 120 transfers."""
    return ({**config, "history": {"days": 1.0, "transfers_per_day": 120,
                                   "seed": 17}},
            {**traffic, "sessions": 24})


def plant(kind: str, monkeypatch, config: dict) -> None:
    """Break ``run_fleet`` as ``kind`` says, for every later call."""
    import repro.core as core
    from benchmarks.chip.drivers.fleet import queued_pair

    orig = core.run_fleet
    first = {}

    def _first(rep, i):
        return next(k for k, s in enumerate(rep.sessions)
                    if s.request_index == i and s.attempt == 0)

    def _session(rep, k, **report):
        s = rep.sessions[k]
        rep.sessions[k] = dataclasses.replace(
            s, report=dataclasses.replace(s.report, **report))

    def broken(db, reqs, engine=None):
        if kind == "state_unchanged":
            if "report" not in first:
                first["report"] = orig(db, reqs, engine)
            return first["report"]
        if kind == "half_batch":
            return orig(db, reqs[::2], engine)
        if kind == "cap_shifted":
            # admission at one session more than the scoring gives
            cap = orig(db, reqs, engine).admitted_concurrency
            return orig(db, reqs,
                        dataclasses.replace(engine, max_concurrent=cap + 1))
        rep = orig(db, reqs, engine)
        arrival = [r.start_clock_s for r in reqs]
        if kind == "cap_reported_low":
            rep.admitted_concurrency -= 1
        elif kind == "over_link":
            _session(rep, 0,
                     achieved_mbps=1.01 * config["link"]["bandwidth_mbps"])
        elif kind == "params_shifted":
            top = config["param_domain"]["cc"]
            for k, s in enumerate(rep.sessions):
                prm = s.report.params
                _session(rep, k, params=dataclasses.replace(
                    prm, cc=prm.cc + 1 if prm.cc < top else prm.cc - 1))
        elif kind == "admitted_early":
            # the last request to arrive admitted a second before it arrived
            last = max(range(len(reqs)), key=lambda i: (arrival[i], i))
            k = _first(rep, last)
            rep.sessions[k] = dataclasses.replace(
                rep.sessions[k], admit_s=arrival[last] - 1.0)
        elif kind == "queue_jumped":
            # the first and the last queued request admitted in each
            # other's turn
            a, b = (_first(rep, i) for i in
                    queued_pair(arrival, rep.admitted_concurrency))
            sa, sb = rep.sessions[a], rep.sessions[b]
            rep.sessions[a] = dataclasses.replace(sa, admit_s=sb.admit_s)
            rep.sessions[b] = dataclasses.replace(sb, admit_s=sa.admit_s)
        elif kind == "answer_altered":
            _session(rep, 0, moved_mb=0.5 * rep.sessions[0].report.moved_mb)
        else:
            raise ValueError(f"unknown fault {kind!r}")
        return rep

    monkeypatch.setattr(core, "run_fleet", broken)
