"""Test support of the ``fleet_load`` driver: the ``fleet`` driver's tiny
size, faults and recorded trace, and two faults of the shared link load
planted under the timed path."""
from __future__ import annotations

import dataclasses
import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "bench_support_fleet_for_load", pathlib.Path(__file__).with_name("fleet.py"))
_fleet = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_fleet)

RECORDED = _fleet.RECORDED
FAULTS = _fleet.FAULTS + ["load_unshared", "per_tenant_load"]
shrink = _fleet.shrink


def plant(kind: str, monkeypatch, config: dict) -> None:
    """Break ``run_fleet`` as ``kind`` says, for every later call."""
    if kind not in ("load_unshared", "per_tenant_load"):
        _fleet.plant(kind, monkeypatch, config)
        return
    import repro.core as core
    from repro.netsim.testbeds import make_traffic

    orig = core.run_fleet

    def broken(db, reqs, engine=None):
        if kind == "per_tenant_load":
            # every tenant on a load of its own, whose walk steps per reading
            return orig(db, [dataclasses.replace(
                r, traffic=make_traffic(config["testbed"], seed=r.env_seed))
                for r in reqs], engine)
        rep = orig(db, reqs, engine)
        # one chunk of the first session reports a load 0.01 off the link's
        rec = rep.sessions[0].report.samples[0]
        rep.sessions[0].report.samples[0] = dataclasses.replace(
            rec, ext_load=rec.ext_load + 0.01)
        return rep

    monkeypatch.setattr(core, "run_fleet", broken)
