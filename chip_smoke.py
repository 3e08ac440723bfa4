#!/usr/bin/env python3
"""Bring-up smoke of the tuner's device path on one TPU chip.

Drives the main path once through the entry points a user calls, at the
size the repo's full-size benchmarks claim, with every Pallas kernel on
(``use_pallas=True``), and compares each phase with its plain reference:

* ``discovery`` -- ``fit_clusters`` over 1e6 seeded log rows, m in 4..12,
  against the same fit on the fused XLA label pass and against float64
  numpy Lloyd polished from the device centroids; then ``offline_analysis``
  over a seeded multi-network Globus-schema history;
* ``refit``     -- ``OfflineDB.update`` with >= 512 new entries (the
  ``cluster_assign`` and ``spline_fit`` kernels) against the XLA refit;
* ``fleet``     -- ``run_fleet`` on the vectorized engine at 2,000 sessions
  (admission scoring through ``transfer_select``) against the XLA run, and
  ``SurfaceStack.best_candidates`` at 256 requests x 16 candidates against
  ``kernels.ref.batched_predict_argmax_ref``, exactly;
* ``service``   -- ``KnowledgeService.query`` answers and one ingest that
  forces a refit, against the XLA service.

``--chips 4`` runs only the ICI collective bridge: ``bucketed_allreduce`` and
``quantized_allreduce`` under ``shard_map`` on a 4-device ``data`` mesh,
with the bucket plan the tuner picks on the ICI model, against a plain
``lax.psum`` of the same gradient tree.

Each phase prints one JSON line; wall and compile seconds on those lines
are bring-up observations, not metrics.  The last line is
``{"ok": true, "device": {...}}`` and is printed only when every phase
passed.  With no TPU the script exits non-zero before any phase runs.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # collective bridge on four chips
"""
from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import math
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))


@dataclasses.dataclass(frozen=True)
class Sizes:
    """How much work each phase does; ``FULL`` is what the script runs."""

    discovery_rows: int = 1_000_000
    m_range: range = range(4, 13)
    history_days: float = 14.0
    history_per_day: int = 220
    refit_entries: int = 600
    fleet_sessions: int = 2_000
    select_requests: int = 256
    select_candidates: int = 16
    service_queries: int = 3_000
    service_ingest: int = 600
    grad_elems: int = 8_388_608


FULL = Sizes()

# Label agreement the pallas fit must reach with the XLA fit and with
# float64 Lloyd polished from its centroids (CPU fixed point: 0.9998).
MIN_AGREEMENT = 0.999
# Relative tolerance of float32 spline refits (Pallas vs XLA Thomas solve).
F32_RTOL = 1e-4


# ------------------------------------------------------------------ #
# compile-time bookkeeping
# ------------------------------------------------------------------ #
class CompileClock:
    """Sums XLA backend-compile seconds and persistent-cache hits/misses."""

    def __init__(self) -> None:
        import jax.monitoring

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple[float, int, int, int]:
        return self.seconds, self.compiles, self.hits, self.misses


def run_phase(name: str, fn, clock: CompileClock | None, results: list) -> object:
    """Run one phase, print its JSON line, record pass/fail.

    A phase that raises is recorded as failed (the run then exits
    non-zero) and the later phases still run, so one chip call shows every
    phase that breaks.
    """
    before = clock.snapshot() if clock else (0.0, 0, 0, 0)
    t0 = time.perf_counter()
    value = None
    try:
        checks, value = fn()
        ok = all(v for k, v in checks.items() if k.startswith("pass_"))
    except Exception as e:  # recorded as a failed phase, never swallowed
        traceback.print_exc()
        checks, ok = {"error": f"{type(e).__name__}: {e}"}, False
    after = clock.snapshot() if clock else (0.0, 0, 0, 0)
    line = {
        "phase": name,
        "ok": ok,
        "checks": checks,
        "bring_up_observation": {
            "wall_s": time.perf_counter() - t0,
            "compile_s": after[0] - before[0],
            "compiles": after[1] - before[1],
            "cache_hits": after[2] - before[2],
            "cache_misses": after[3] - before[3],
        },
    }
    print(json.dumps(line, default=_json_default), flush=True)
    results.append((name, ok))
    return value


def _json_default(o):
    if isinstance(o, (np.floating, np.integer, np.bool_)):
        return o.item()
    raise TypeError(type(o).__name__)


# ------------------------------------------------------------------ #
# one-chip phases
# ------------------------------------------------------------------ #
def phase_fit_clusters(sz: Sizes):
    from repro.core.clustering import fit_clusters, kmeans, label_agreement
    from repro.netsim import sample_feature_logs

    X = sample_feature_logs(sz.discovery_rows, seed=7)
    t0 = time.perf_counter()
    pal = fit_clusters(X, m_range=sz.m_range, seed=0, batched=True,
                       use_pallas=True)
    t_pal = time.perf_counter() - t0
    t0 = time.perf_counter()
    xla = fit_clusters(X, m_range=sz.m_range, seed=0, batched=True,
                       use_pallas=False)
    t_xla = time.perf_counter() - t0
    polished, _ = kmeans(X, pal.m, init=pal.centroids)
    agree_xla = label_agreement(pal.labels, xla.labels)
    agree_fp = label_agreement(pal.labels, polished)
    return {
        "rows": int(X.shape[0]),
        "m_pallas": pal.m,
        "m_xla": xla.m,
        "agree_pallas_vs_xla": agree_xla,
        "agree_pallas_vs_f64_lloyd": agree_fp,
        "fit_s_pallas": t_pal,
        "fit_s_xla": t_xla,
        "pass_same_order": pal.m == xla.m,
        "pass_agree_xla": agree_xla >= MIN_AGREEMENT,
        "pass_agree_f64_lloyd": agree_fp >= MIN_AGREEMENT,
    }, None


def phase_offline_analysis(sz: Sizes):
    from repro.core.clustering import label_agreement
    from repro.core.offline import offline_analysis
    from repro.netsim import generate_multi_network_history

    hist = generate_multi_network_history(
        days=sz.history_days, transfers_per_day=sz.history_per_day, seed=0)
    split = int(0.8 * len(hist))  # time-sorted: the tail arrives later
    train, fresh = hist[:split], hist[split:]
    db = offline_analysis(train, batched=True, use_pallas=True)
    ref = offline_analysis(train, batched=True, use_pallas=False)
    agree = label_agreement(db.cluster_model.labels, ref.cluster_model.labels)
    same_surfaces = _max_surface_gap(db, ref, range(db.cluster_model.m))
    return {
        "entries": len(train),
        "m_pallas": db.cluster_model.m,
        "m_xla": ref.cluster_model.m,
        "agree_pallas_vs_xla": agree,
        "max_rel_surface_gap": same_surfaces,
        "pass_same_order": db.cluster_model.m == ref.cluster_model.m,
        "pass_same_labels": agree == 1.0,
        "pass_surfaces": same_surfaces <= F32_RTOL,
    }, (db, fresh)


def _max_surface_gap(a, b, clusters) -> float:
    """Largest relative gap between two DBs' dense lattice surfaces.

    inf when the clusters differ in surface count or load tags."""
    gap = 0.0
    for k in clusters:
        sa = a.clusters[k].surface_stack(a.bounds)
        sb = b.clusters[k].surface_stack(b.bounds)
        if sa.values.shape != sb.values.shape or not np.array_equal(
                np.asarray(sa.load), np.asarray(sb.load)):
            return math.inf
        va, vb = np.asarray(sa.values), np.asarray(sb.values)
        scale = max(float(np.abs(vb).max()), 1e-9)
        gap = max(gap, float(np.abs(va - vb).max()) / scale)
    return gap


def phase_refit(sz: Sizes, db, fresh):
    new = fresh[:sz.refit_entries]
    pal, ref = copy.deepcopy(db), copy.deepcopy(db)
    F = np.stack([e.features() for e in new])
    lab_pal = pal.cluster_model.assign_many(F, use_pallas=True)
    lab_ref = ref.cluster_model.assign_many(F)
    t0 = time.perf_counter()
    touched = pal.update(new, batched_fit=True, use_pallas=True)
    t_pal = time.perf_counter() - t0
    touched_ref = ref.update(new, batched_fit=True, use_pallas=False)
    gap = (_max_surface_gap(pal, ref, sorted(touched))
           if touched == touched_ref else math.inf)
    return {
        "new_entries": len(new),
        "assign_mismatches": int((lab_pal != lab_ref).sum()),
        "touched": sorted(touched),
        "max_rel_surface_gap": gap,
        "update_s_pallas": t_pal,
        "pass_kernel_path": len(new) >= 512,
        "pass_same_assignments": bool((lab_pal == lab_ref).all()),
        "pass_surfaces": gap <= F32_RTOL,
    }, None


def _fleet_requests(n: int):
    from repro.core import FleetRequest
    from repro.netsim import make_dataset

    classes = ["small", "medium", "large"]
    return [FleetRequest(dataset=make_dataset(classes[i % 3], 30 + i),
                         env_seed=500 + i, start_clock_s=4 * 3600.0,
                         constant_load=0.15) for i in range(n)]


def _same_fleet(a, b) -> bool:
    """FleetReport equality, where an unscored run's accuracy is NaN."""
    if math.isnan(a.accuracy_vs_single) and math.isnan(b.accuracy_vs_single):
        a = dataclasses.replace(a, accuracy_vs_single=0.0)
        b = dataclasses.replace(b, accuracy_vs_single=0.0)
    return a == b


def phase_fleet(sz: Sizes, db):
    from repro.core import EngineConfig, run_fleet
    from repro.core.fleet import predict_demands

    reqs = _fleet_requests(sz.fleet_sessions)
    dem_pal = predict_demands(db, reqs, use_pallas=True)
    dem_ref = predict_demands(db, reqs)
    t0 = time.perf_counter()
    pal = run_fleet(db, list(reqs), EngineConfig(
        engine="vectorized", use_pallas=True, score_vs_single=False))
    t_pal = time.perf_counter() - t0
    ref = run_fleet(db, list(reqs), EngineConfig(
        engine="vectorized", use_pallas=False, score_vs_single=False))
    return {
        "sessions": len(reqs),
        "admitted_concurrency": pal.admitted_concurrency,
        "goodput_mbps": pal.goodput_mbps,
        "run_s_pallas": t_pal,
        "pass_demands_exact": bool(np.array_equal(dem_pal, dem_ref)),
        "pass_report_equal": _same_fleet(pal, ref),
    }, None


def phase_select(sz: Sizes, db):
    from repro.kernels.ref import batched_predict_argmax_ref

    rng = np.random.default_rng(11)
    b = db.bounds
    hi = np.array([b.max_cc, b.max_p, b.max_pp])
    mism = 0
    shapes = set()
    for ck in db.clusters:
        stack = ck.surface_stack(b)
        pts = rng.integers(1, hi + 1,
                           size=(sz.select_requests, sz.select_candidates, 3))
        best, argk = stack.best_candidates(pts, use_pallas=True)
        best_r, argk_r = batched_predict_argmax_ref(stack.flat_values,
                                                    stack.flat_index(pts))
        mism += int((np.asarray(best) != np.asarray(best_r)).sum()
                    + (np.asarray(argk) != np.asarray(argk_r)).sum())
        shapes.add((int(stack.n_surfaces), int(stack.flat_values.shape[1])))
    return {
        "requests": sz.select_requests,
        "candidates": sz.select_candidates,
        "stacks_SG": sorted(shapes),
        "mismatches": mism,
        "pass_exact": mism == 0,
    }, None


def phase_service(sz: Sizes, db, fresh):
    from repro.core import KnowledgeService, ServiceConfig

    feats = [e.features() for ck in db.clusters for e in ck.entries]
    queries = [feats[i % len(feats)] for i in range(sz.service_queries)]
    batch = fresh[-sz.service_ingest:]

    def serve(use_pallas: bool):
        # a tiny drift bound: the ingested batch forces refits of every
        # cluster it touches
        svc = KnowledgeService(copy.deepcopy(db), ServiceConfig(
            max_staleness_s=None, drift_threshold=1e-9,
            use_pallas=use_pallas))
        before = [svc.query(None, f) for f in queries]
        refit = svc.ingest(batch, now_s=batch[-1].timestamp_s)
        after = [svc.query(None, f) for f in queries]
        return svc, before + after, refit

    t0 = time.perf_counter()
    svc, ans, refit = serve(True)
    t_pal = time.perf_counter() - t0
    _, ans_ref, refit_ref = serve(False)
    same_params = sum(a.params == r.params and a.cluster_index == r.cluster_index
                      for a, r in zip(ans, ans_ref))
    rel = max(abs(a.predicted_mbps - r.predicted_mbps)
              / max(abs(r.predicted_mbps), 1e-9) for a, r in zip(ans, ans_ref))
    st = svc.stats()
    return {
        "queries": len(ans),
        "ingested": len(batch),
        "refits": st.refits,
        "refit_clusters": sorted(next(iter(refit.values()), set())),
        "same_answers": same_params,
        "max_rel_mbps_gap": rel,
        "service_s_pallas": t_pal,
        "pass_refit_forced": st.refits > 0 and refit == refit_ref,
        "pass_same_answers": same_params == len(ans),
        "pass_mbps": rel <= F32_RTOL,
    }, None


# ------------------------------------------------------------------ #
# four-chip phase: the ICI collective bridge
# ------------------------------------------------------------------ #
def tuned_bucket_plan():
    """The bucket plan the tuner converges to on the ICI fabric model."""
    from repro.core import TransferTuner, TunerConfig
    from repro.dist.collectives import ici_environment, plan_from_tuner_params
    from repro.netsim import generate_history
    from repro.netsim.workload import Dataset

    hist = generate_history(ici_environment(seed=0), days=2,
                            transfers_per_day=150, seed=1)
    tuner = TransferTuner(TunerConfig(seed=0)).fit(hist)
    grads = Dataset("gradients", "large", avg_file_mb=1600.0, n_files=64)
    rep = tuner.transfer(ici_environment(seed=9), grads)
    return plan_from_tuner_params(rep.params)


def grad_tree_shapes(n_elems: int) -> dict:
    """A layer's gradient tree: embedding, MLP pair, bias and norm leaves."""
    d = 512
    vocab = max(n_elems // 2 // d, 1)
    ff = max(n_elems // 4 // d, 1)
    return {"embed": (vocab, d), "mlp": {"w_in": (d, ff), "w_out": (ff, d),
                                         "b_in": (ff,)}, "norm": (d,)}


def allreduce_fns(mesh, plan):
    """Jitted (bucketed, quantized, plain psum) all-reduces of a tree whose
    leaves are stacked over the mesh's ``data`` axis."""
    import jax
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    from repro.dist.collectives import (bucketed_allreduce, flatten_grads,
                                        quantized_allreduce, unflatten_grads)

    def bridged(reduce):
        def body(tree):
            local = jax.tree.map(lambda l: l[0], tree)
            flat, spec = flatten_grads(local)
            out = unflatten_grads(reduce(flat, plan, "data"), spec)
            return jax.tree.map(lambda l: l[None], out)
        return body

    def plain(tree):
        return jax.tree.map(lambda l: lax.psum(l, "data"), tree)

    def wrap(body):
        return jax.jit(shard_map(body, mesh=mesh, in_specs=P("data"),
                                 out_specs=P("data"), check_vma=False))

    return (wrap(bridged(bucketed_allreduce)),
            wrap(bridged(quantized_allreduce)), wrap(plain))


def all_reduce_group_sizes(hlo: str) -> list[int]:
    """Participant count of every all-reduce in compiled HLO text."""
    import re

    sizes = []
    for line in hlo.splitlines():
        if not re.search(r"\ball-reduce(-start)?\(", line):
            continue
        m = re.search(r"replica_groups=\{\{([0-9,]*)\}", line)
        if m:
            sizes.append(len(m.group(1).split(",")))
            continue
        m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=", line)
        sizes.append(int(m.group(2)) if m else 0)
    return sizes


def phase_collectives(sz: Sizes, n_dev: int):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()[:n_dev]
    if len(devices) < n_dev:
        raise RuntimeError(f"need {n_dev} devices, found {len(devices)}")
    plan = tuned_bucket_plan()
    mesh = Mesh(np.array(devices), ("data",))
    bucketed, quantized, plain = allreduce_fns(mesh, plan)
    rng = np.random.default_rng(3)
    # values on a 1/256 grid with |x| < 8: any summation order is exact in
    # f32, so the bucketed reduction must match psum bit for bit
    tree = jax.tree.map(
        lambda s: np.round(rng.normal(size=(n_dev, *s)) * 256.0).clip(
            -2047, 2047).astype(np.float32) / 256.0,
        grad_tree_shapes(sz.grad_elems), is_leaf=lambda s: isinstance(s, tuple))
    x = jax.device_put(tree, NamedSharding(mesh, P("data")))
    want = plain(x)
    got_b = bucketed(x)
    got_q = quantized(x)
    leaves_w = [np.asarray(v) for v in jax.tree.leaves(want)]
    leaves_b = [np.asarray(v) for v in jax.tree.leaves(got_b)]
    leaves_q = [np.asarray(v) for v in jax.tree.leaves(got_q)]
    exact = all(np.array_equal(a, b) for a, b in zip(leaves_b, leaves_w))
    # each participant rounds to half an int8 step of the agreed scale
    max_abs = max(float(np.abs(v).max()) for v in jax.tree.leaves(tree))
    bound = n_dev * 0.5 * (max_abs / 127.0) * (1 + 1e-5) + 1e-6
    q_err = max(float(np.abs(a - b).max()) for a, b in zip(leaves_q, leaves_w))
    spans = []
    for fn in (bucketed, quantized):
        spans += all_reduce_group_sizes(fn.lower(x).compile().as_text())
    out_devices = {d.id for v in jax.tree.leaves(got_b)
                   for d in v.sharding.device_set}
    return {
        "devices": n_dev,
        "plan": dataclasses.asdict(plan),
        "elements_per_device": sum(int(np.prod(v.shape[1:]))
                                   for v in jax.tree.leaves(tree)),
        "all_reduces": len(spans),
        "all_reduce_group_sizes": sorted(set(spans)),
        "quantized_max_err": q_err,
        "quantized_bound": bound,
        "pass_bucketed_exact": exact,
        "pass_quantized_bound": q_err <= bound,
        "pass_spans_all_devices": bool(spans) and all(
            s == n_dev for s in spans),
        "pass_output_on_all_devices": len(out_devices) == n_dev,
    }, None


# ------------------------------------------------------------------ #
def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the ICI collective bridge on a "
                         "four-device mesh")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU device found (jax.devices()[0] is "
              f"{dev.platform!r}); this smoke runs only on a TPU",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    results: list = []
    t0 = time.perf_counter()
    sz = FULL
    if args.chips == 4:
        run_phase("collectives", lambda: phase_collectives(sz, 4), clock,
                  results)
    else:
        run_phase("discovery_fit_clusters", lambda: phase_fit_clusters(sz),
                  clock, results)
        out = run_phase("discovery_offline_analysis",
                        lambda: phase_offline_analysis(sz), clock, results)
        db, fresh = out if out is not None else (None, None)
        if db is not None:
            run_phase("refit", lambda: phase_refit(sz, db, fresh), clock,
                      results)
            run_phase("fleet", lambda: phase_fleet(sz, db), clock, results)
            run_phase("batched_select", lambda: phase_select(sz, db), clock,
                      results)
            run_phase("service", lambda: phase_service(sz, db, fresh),
                      clock, results)
    stats = dev.memory_stats() or {}
    print(json.dumps({"bring_up_observation": {
        "device_kind": dev.device_kind,
        "wall_s": time.perf_counter() - t0,
        "compile_s": clock.seconds,
        "compiles": clock.compiles,
        "cache_hits": clock.hits,
        "cache_misses": clock.misses,
        "cache_dir": cache_dir,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }}), flush=True)
    failed = [name for name, ok in results if not ok]
    if failed:
        print(f"chip_smoke: phases failed: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
