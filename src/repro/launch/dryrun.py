import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) for
the production meshes, print memory/cost analysis, and dump roofline inputs.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-405b \
        --shape train_4k [--multi-pod] [--all] [--out results.json]

The XLA_FLAGS line above MUST precede any jax import: jax locks the device
count at first init, and the dry-run needs 512 placeholder host devices for
jax.make_mesh to build the 2x16x16 production mesh.  Nothing here allocates
real buffers — inputs are ShapeDtypeStructs and parameters come from
abstract init.
"""
import argparse
import json
import re
import time
import traceback

import jax

from repro.configs import all_archs, get_config
from repro.dist.sharding import (ShardingReport, batch_sharding,
                                 default_rules, replicated, tree_shardings)
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import (LONG_CONTEXT_OK, SHAPES, TRAIN_MICROBATCHES,
                                 applicable_cells, input_specs)
from repro.models.model import build_model
from repro.train.loop import TrainConfig, make_train_step

_COLL_RE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"[^=]*=\s*(?:\([^)]*\)|(\w+)\[([0-9,]+)\])")


def _dtype_bytes(name: str) -> int:
    return {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
            "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8}.get(name, 4)


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Sum output-shape bytes of every collective op in the partitioned HLO."""
    out: dict[str, float] = {}
    # ops look like:  %x = bf16[16,1024]{...} all-reduce(...), or tuples
    pat = re.compile(
        r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\][^ ]*))\s+"
        r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
        r"(?:-start|-done)?\(")
    shape_pat = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
    for shapes, op in pat.findall(hlo_text):
        total = 0
        for dt, dims in shape_pat.findall(shapes):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _dtype_bytes(dt)
        out[op] = out.get(op, 0.0) + float(total)
    return out


def run_cost_cell(arch: str, shape_name: str, *, verbose: bool = True) -> dict:
    """Accurate HLO cost for the roofline: XLA's cost_analysis counts a
    lax.scan body once regardless of trip count, so the full-depth scanned
    lowering (memory mode) undercounts FLOPs by ~n_layers.  Here we compile
    *unrolled* models at two depths at full width on the production mesh,
    take the per-layer slope, and extrapolate to the real depth.  Attention
    is materialized (no inner scans) — nothing is allocated during lowering,
    so the S^2 logits tensors exist only as HLO metadata.
    """
    import dataclasses as dc

    from repro.kernels import ops as kops
    cfg0 = get_config(arch, "full")
    shape = SHAPES[shape_name]

    old_thresh = kops.BLOCKED_ATTENTION_THRESHOLD
    kops.BLOCKED_ATTENTION_THRESHOLD = 1 << 62     # force materialized
    try:
        if cfg0.hybrid_attn_every:
            k = cfg0.hybrid_attn_every
            depths = [k, 2 * k]
            n_units = cfg0.n_layers / k            # fractional final group
        elif cfg0.first_k_dense:
            depths = [cfg0.first_k_dense + 1, cfg0.first_k_dense + 2]
            n_units = cfg0.n_layers - cfg0.first_k_dense
        else:
            depths = [1, 2]
            n_units = cfg0.n_layers

        meas = []
        for d in depths:
            cfg = dc.replace(cfg0, n_layers=d, scan_layers=False)
            r = _lower_and_analyze(cfg, arch, shape, multi_pod=False,
                                   micro_override=1, verbose=False)
            meas.append(r)
        f0, f1 = meas[0]["flops_total"], meas[1]["flops_total"]
        b0, b1 = meas[0]["bytes_accessed"], meas[1]["bytes_accessed"]
        c0, c1 = (meas[0]["collective_bytes_total"],
                  meas[1]["collective_bytes_total"])
        slope_f, slope_b, slope_c = f1 - f0, b1 - b0, c1 - c0
        extra = n_units - (1.0 if cfg0.hybrid_attn_every else depths[0]) \
            if not cfg0.first_k_dense else n_units - 1
        if cfg0.first_k_dense:
            extra = n_units - 1
        result = {
            "arch": arch, "shape": shape_name, "mesh": "16x16",
            "mode": "cost",
            "flops_total": f0 + slope_f * extra,
            "bytes_accessed": b0 + slope_b * extra,
            "collective_bytes_total": c0 + slope_c * extra,
            "per_layer_flops": slope_f,
            "depths_measured": depths,
        }
        if verbose:
            print(f"[cost {arch} x {shape_name}] flops={result['flops_total']:.3e} "
                  f"bytes={result['bytes_accessed']:.3e} "
                  f"coll={result['collective_bytes_total']:.3e}")
        return result
    finally:
        kops.BLOCKED_ATTENTION_THRESHOLD = old_thresh


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             verbose: bool = True) -> dict:
    cfg = get_config(arch, "full")
    return _lower_and_analyze(cfg, arch, SHAPES[shape_name],
                              multi_pod=multi_pod, verbose=verbose)


def _lower_and_analyze(cfg, arch, shape, *, multi_pod: bool,
                       micro_override: int | None = None,
                       verbose: bool = True,
                       act_spec="default") -> dict:
    import dataclasses as dc
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    if act_spec == "default":
        # §Perf iteration 5: GQA-MoE archs (mixtral) run 2.6x less collective
        # traffic with the residual stream UNsharded in d_model (the expert
        # dispatch consumes full-d tokens, so the (b,-,model) pin forces
        # per-layer all-gathers).  MLA-MoE (deepseek) is the opposite — its
        # low-rank latents replicate catastrophically without the pin — and
        # dense archs need the pin for activation memory.  Measured A/B in
        # EXPERIMENTS.md §Perf.
        if cfg.n_experts > 0 and cfg.attn_type != "mla":
            act_spec = (batch_axes, None, None)
        else:
            act_spec = (batch_axes, None, "model")
    cfg = dc.replace(cfg, act_spec=act_spec)
    shape_name = shape.name
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = default_rules(multi_pod)
    report = ShardingReport()
    t0 = time.perf_counter()

    with mesh:
        params, axes = model.init(jax.random.PRNGKey(0), abstract=True)
        p_shard = tree_shardings(params, axes, mesh, rules, report)
        specs = input_specs(cfg, shape)

        if shape.kind == "train":
            micro = micro_override or TRAIN_MICROBATCHES.get(
                arch, TRAIN_MICROBATCHES["default"])
            tcfg = TrainConfig(microbatches=micro)
            from repro.optim import adamw_init
            opt = adamw_init(params, tcfg.opt, abstract=True)
            from repro.train.loop import opt_state_axes
            o_shard = tree_shardings(opt, opt_state_axes(axes), mesh, rules,
                                     report)
            step = make_train_step(model, tcfg)
            b_shard = {k: batch_sharding(mesh, ndim=len(v.shape),
                                         batch_size=v.shape[0])
                       for k, v in specs.items()}
            fn = jax.jit(step,
                         in_shardings=(p_shard, o_shard, b_shard),
                         out_shardings=(p_shard, o_shard, replicated(mesh)))
            lowered = fn.lower(params, opt, specs)

        elif shape.kind == "prefill":
            cache, c_axes = model.init_cache(shape.global_batch,
                                             shape.seq_len, abstract=True)
            c_shard = tree_shardings(cache, c_axes, mesh, rules, report)
            b_shard = {k: batch_sharding(mesh, ndim=len(v.shape),
                                         batch_size=v.shape[0])
                       for k, v in specs.items()}
            def prefill(params, specs_in, cache):
                return model.prefill(params, specs_in["tokens"], cache,
                                     specs_in.get("patch_embeds"))
            out_lg = batch_sharding(mesh, ndim=4 if cfg.n_codebooks else 3,
                                    batch_size=shape.global_batch)
            fn = jax.jit(prefill,
                         in_shardings=(p_shard, b_shard, c_shard),
                         out_shardings=(out_lg, c_shard))
            lowered = fn.lower(params, specs, cache)

        else:  # decode
            cache, c_axes = model.init_cache(shape.global_batch,
                                             shape.seq_len, abstract=True)
            c_shard = tree_shardings(cache, c_axes, mesh, rules, report)
            tok_shard = batch_sharding(mesh,
                                       ndim=len(specs["tokens"].shape),
                                       batch_size=shape.global_batch)
            def decode(params, tokens, cache):
                return model.decode(params, tokens, cache)
            out_tok_shard = batch_sharding(
                mesh, ndim=4 if cfg.n_codebooks else 3,
                batch_size=shape.global_batch)
            fn = jax.jit(decode,
                         in_shardings=(p_shard, tok_shard, c_shard),
                         out_shardings=(out_tok_shard, c_shard))
            lowered = fn.lower(params, specs["tokens"], cache)

        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        cost = cost or {}                       # backends without cost model
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)

    n_dev = mesh.devices.size
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(n_dev),
        "flops_total": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": coll,
        "collective_bytes_total": float(sum(coll.values())),
        "bytes_per_device": {
            "argument": float(getattr(mem, "argument_size_in_bytes", 0)),
            "output": float(getattr(mem, "output_size_in_bytes", 0)),
            "temp": float(getattr(mem, "temp_size_in_bytes", 0)),
            "peak": float(getattr(mem, "peak_memory_in_bytes", 0) or
                          (getattr(mem, "argument_size_in_bytes", 0)
                           + getattr(mem, "output_size_in_bytes", 0)
                           + getattr(mem, "temp_size_in_bytes", 0))),
        },
        "degraded_shardings": len(report.degraded),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
    }
    if verbose:
        print(f"[{arch} x {shape_name} @ {result['mesh']}] "
              f"lower {t_lower:.0f}s compile {t_compile:.0f}s")
        print(f"  memory/device: arg={result['bytes_per_device']['argument']/2**30:.2f}GiB "
              f"temp={result['bytes_per_device']['temp']/2**30:.2f}GiB")
        print(f"  flops={result['flops_total']:.3e} "
              f"bytes={result['bytes_accessed']:.3e} "
              f"coll={result['collective_bytes_total']:.3e}")
        if report.degraded:
            kinds = {}
            for pth, dim, why in report.degraded:
                kinds[why.split(' ')[0]] = kinds.get(why.split(' ')[0], 0) + 1
            print(f"  degraded shardings: {kinds}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--cost", action="store_true",
                    help="accurate-cost mode (unrolled 2-depth extrapolation, "
                         "single-pod) for the roofline")
    ap.add_argument("--out", default="dryrun_results.json")
    args = ap.parse_args()

    results = []
    if os.path.exists(args.out):
        results = json.load(open(args.out))
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results}

    if args.all:
        cells = applicable_cells()
    else:
        shapes = [args.shape] if args.shape else list(SHAPES)
        archs = [args.arch] if args.arch else all_archs()
        cells = [(a, s) for a in archs for s in shapes
                 if not (s == "long_500k" and a not in LONG_CONTEXT_OK)]

    if args.cost:
        ok = fail = 0
        for arch, shape in cells:
            if (arch, shape, "16x16") in done:
                continue
            try:
                results.append(run_cost_cell(arch, shape))
                ok += 1
            except Exception as e:
                print(f"[cost {arch} x {shape}] FAILED: {e}")
                traceback.print_exc()
                results.append({"arch": arch, "shape": shape,
                                "mesh": "16x16", "error": str(e)[:500]})
                fail += 1
            json.dump(results, open(args.out, "w"), indent=1)
        print(f"cost dry-run complete: {ok} ok, {fail} failed -> {args.out}")
        return

    meshes = [False, True] if (args.both_meshes or args.all) \
        else [args.multi_pod]
    ok = fail = 0
    for arch, shape in cells:
        for mp in meshes:
            mesh_name = "2x16x16" if mp else "16x16"
            if (arch, shape, mesh_name) in done:
                continue
            try:
                r = run_cell(arch, shape, multi_pod=mp)
                results.append(r)
                ok += 1
            except Exception as e:
                print(f"[{arch} x {shape} @ {mesh_name}] FAILED: {e}")
                traceback.print_exc()
                results.append({"arch": arch, "shape": shape,
                                "mesh": mesh_name, "error": str(e)[:500]})
                fail += 1
            json.dump(results, open(args.out, "w"), indent=1)
    print(f"dry-run complete: {ok} ok, {fail} failed -> {args.out}")


if __name__ == "__main__":
    main()
