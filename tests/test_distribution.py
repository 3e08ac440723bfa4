"""Distribution substrates: sharding rules, collectives, optimizer,
checkpointing, elastic recovery, straggler detection, data pipeline."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.checkpoint.ckpt import (CkptParams, latest_step, prune_checkpoints,
                                   restore_checkpoint, save_checkpoint)
from repro.data.pipeline import DataConfig, PipelineParams, TokenPipeline
from repro.dist.collectives import (BucketPlan, allreduce_bytes,
                                    bucketed_allreduce, flatten_grads,
                                    ici_environment, plan_from_tuner_params,
                                    quantized_allreduce, unflatten_grads)
from repro.dist.sharding import (ShardingReport, default_rules, spec_for)
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.optim.grad_utils import (clip_by_global_norm, dequantize_int8,
                                    global_norm, quantize_int8)
from repro.train.elastic import plan_mesh
from repro.train.straggler import (StragglerDetector, StragglerPolicy,
                                   rebalance_buckets)


class _FakeMesh:
    """Just enough of a Mesh for spec_for (shape lookup)."""
    def __init__(self, shape: dict):
        self.shape = shape


# ----------------------------- sharding ------------------------------- #
def test_spec_divisible_dims_shard():
    mesh = _FakeMesh({"data": 16, "model": 16})
    rules = default_rules(False)
    spec = spec_for((16384, 128, 128), ("embed", "heads", "head_dim"),
                    rules, mesh)
    assert spec == P("data", "model")


def test_spec_degrades_non_divisible():
    mesh = _FakeMesh({"data": 16, "model": 16})
    rules = default_rules(False)
    rep = ShardingReport()
    # 40 heads don't divide 16 -> replicated, reported
    spec = spec_for((5120, 40, 128), ("embed", "heads", "head_dim"),
                    rules, mesh, rep, "wq")
    assert spec == P("data")
    assert rep.degraded


def test_spec_partial_prefix_drop_keeps_divisible_suffix():
    mesh = _FakeMesh({"pod": 2, "data": 4, "model": 16})
    rules = default_rules(True)
    rep = ShardingReport()
    # 12 doesn't divide pod*data = 8 — but instead of degrading straight to
    # replicated, the outer pod axis drops and the batch still shards 4-way.
    spec = spec_for((12, 64), ("batch", None), rules, mesh, rep, "x")
    assert spec == P("data")
    assert len(rep.degraded) == 1
    path, axis, why = rep.degraded[0]
    assert (path, axis) == ("x", "batch")
    assert why.startswith("partial:")
    assert "kept ('data',)" in why


def test_spec_indivisible_after_all_drops_replicates():
    mesh = _FakeMesh({"pod": 2, "data": 4})
    rules = default_rules(True)
    rep = ShardingReport()
    # 7 divides neither 8, nor 4 after the pod drop -> fully replicated.
    spec = spec_for((7,), ("batch",), rules, mesh, rep, "y")
    assert spec == P()
    assert len(rep.degraded) == 1
    assert "indivisible" in rep.degraded[0][2]


def test_spec_one_axis_per_tensor():
    mesh = _FakeMesh({"data": 16, "model": 16})
    rules = default_rules(False)
    # experts takes 'model'; expert_mlp must NOT reuse it
    spec = spec_for((256, 7168, 2048), ("experts", "embed", "expert_mlp"),
                    rules, mesh)
    assert spec == P("model", "data")


def test_spec_multipod_batch():
    mesh = _FakeMesh({"pod": 2, "data": 16, "model": 16})
    rules = default_rules(True)
    spec = spec_for((256, 4096), ("batch", "seq"), rules, mesh)
    assert spec == P(("pod", "data"))


# ---------------------------- collectives ----------------------------- #
def test_flatten_roundtrip():
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    flat, spec = flatten_grads(tree)
    back = unflatten_grads(flat, spec)
    assert back["a"].shape == (2, 3) and back["b"]["c"].dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(back["a"]), np.arange(6).reshape(2, 3))


def test_bucketed_allreduce_single_device():
    # axis of size 1: psum is identity; checks bucketing/padding plumbing
    from jax import shard_map
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    x = jnp.arange(37, dtype=jnp.float32)
    plan = BucketPlan(n_buckets=3, chunks_per_bucket=2)
    fn = shard_map(lambda v: bucketed_allreduce(v, plan, "data"),
                   mesh=mesh, in_specs=(P(),), out_specs=P(),
                   check_vma=False)
    np.testing.assert_allclose(np.asarray(fn(x)), np.asarray(x))


def test_quantized_allreduce_accuracy():
    from jax import shard_map
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    x = jnp.asarray(np.random.default_rng(0).normal(size=257), jnp.float32)
    plan = BucketPlan(n_buckets=2, chunks_per_bucket=1)
    fn = shard_map(lambda v: quantized_allreduce(v, plan, "data"),
                   mesh=mesh, in_specs=(P(),), out_specs=P(),
                   check_vma=False)
    got = np.asarray(fn(x))
    # int8 quantization: ~1% relative error on the bucket scale
    assert np.abs(got - np.asarray(x)).max() <= np.abs(x).max() / 127.0 + 1e-6


def test_quantize_roundtrip():
    x = jnp.asarray(np.random.default_rng(1).normal(size=100), jnp.float32)
    q, s = quantize_int8(x)
    back = dequantize_int8(q, s)
    assert float(jnp.abs(back - x).max()) <= float(s) * 0.51


def test_ici_environment_tuner_integration():
    """The paper's tuner runs against the ICI fabric model end to end."""
    from repro.core import TransferTuner, TunerConfig
    from repro.netsim.loggen import generate_history
    from repro.netsim.workload import Dataset
    env = ici_environment(seed=0)
    hist = generate_history(env, days=2, transfers_per_day=150, seed=1)
    tuner = TransferTuner(TunerConfig(seed=0)).fit(hist)
    env2 = ici_environment(seed=9)
    ds = Dataset("grads", "large", avg_file_mb=1600.0, n_files=64)
    rep = tuner.transfer(env2, ds)
    assert rep.achieved_mbps > 0
    plan = plan_from_tuner_params(rep.params)
    assert plan.n_buckets >= 1 and plan.chunks_per_bucket >= 1


def test_allreduce_bytes():
    assert allreduce_bytes(100, 4) == 800.0


# ----------------------------- optimizer ------------------------------ #
def test_adamw_converges_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, moment_dtype=jnp.float32)
    params = {"w": jnp.array([5.0, -3.0])}
    opt = adamw_init(params, cfg)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}        # d/dw (w^2)
        params, opt = adamw_update(grads, opt, params, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.05


def test_adamw_bf16_moments_close_to_f32():
    k = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(k, (32,))}
    g = {"w": jax.random.normal(jax.random.fold_in(k, 1), (32,))}
    out = {}
    for name, mdt in [("f32", jnp.float32), ("bf16", jnp.bfloat16)]:
        cfg = AdamWConfig(lr=1e-2, moment_dtype=mdt)
        opt = adamw_init(params, cfg)
        p = params
        for _ in range(10):
            p, opt = adamw_update(g, opt, p, cfg)
        out[name] = p["w"]
    err = float(jnp.abs(out["f32"] - out["bf16"]).max())
    assert err < 5e-3, err


def test_grad_clip():
    tree = {"a": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert abs(float(global_norm(clipped)) - 1.0) < 1e-5
    assert float(norm) == pytest.approx(20.0)


# ---------------------------- checkpointing --------------------------- #
def test_checkpoint_roundtrip_and_pruning(tmp_path):
    tree = {"layers": {"w": np.arange(1000, dtype=np.float32).reshape(10, 100),
                       "b": np.ones((7,), np.float32)},
            "embed": np.random.default_rng(0).normal(size=(64, 8)).astype(
                np.bfloat16 if hasattr(np, "bfloat16") else np.float32)}
    d = str(tmp_path / "ckpt")
    for step in (1, 2, 3, 4):
        stats = save_checkpoint(d, step, tree,
                                params=CkptParams(cc=3, p=2, pp=2),
                                log_path=str(tmp_path / "log.jsonl"))
        assert stats["throughput_mbps"] > 0
    assert latest_step(d) == 4
    back = restore_checkpoint(d)
    np.testing.assert_allclose(back["layers"]["w"], tree["layers"]["w"])
    np.testing.assert_allclose(back["layers"]["b"], tree["layers"]["b"])
    prune_checkpoints(d, keep=2)
    assert latest_step(d) == 4
    assert len(os.listdir(d)) == 2
    # transfer log accumulated for offline tuning
    assert sum(1 for _ in open(tmp_path / "log.jsonl")) == 4


def test_checkpoint_crash_safety(tmp_path):
    """An interrupted save (temp dir left behind) must not break restore."""
    tree = {"w": np.ones((16,), np.float32)}
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 1, tree)
    os.makedirs(os.path.join(d, ".tmp_step_00000002"))  # simulated crash
    assert latest_step(d) == 1
    back = restore_checkpoint(d)
    np.testing.assert_allclose(back["w"], tree["w"])


# ------------------------------ elastic ------------------------------- #
def test_plan_mesh_shrinks_on_failure():
    p = plan_mesh(256, model_parallel=16)
    assert p.shape == (16, 16)
    p = plan_mesh(240, model_parallel=16)     # lost a host (16 chips)
    assert p.shape == (8, 16) and p.n_devices == 128
    p = plan_mesh(8, model_parallel=16)       # fleet smaller than TP
    assert p.shape[1] <= 8 and p.n_devices <= 8


# ----------------------------- straggler ------------------------------ #
def test_straggler_detection_and_eviction():
    det = StragglerDetector(8, StragglerPolicy(evict_after=3))
    base = np.full(8, 1.0)
    for i in range(5):
        times = base.copy()
        times[3] = 3.0                        # host 3 is persistently slow
        out = det.record(times)
    assert 3 in out["flagged"]
    assert 3 in out["evict"]
    w = det.shard_weights()
    assert w[3] == min(w)                     # gets the least input work
    assert rebalance_buckets(16, out["slowdown"]) < 16
    assert rebalance_buckets(16, 1.0) == 16


# --------------------------- data pipeline ---------------------------- #
def test_token_pipeline_determinism_and_prefetch():
    cfg = DataConfig(vocab_size=100, global_batch=8, seq_len=16, seed=3)
    p1 = TokenPipeline(cfg, PipelineParams(cc=2, p=2, pp=3))
    batches1 = [p1.next_batch() for _ in range(3)]
    p1.close()
    for b in batches1:
        assert b["tokens"].shape == (8, 16)
        assert b["tokens"].min() >= 0 and b["tokens"].max() < 100
    # pipeline keeps producing under prefetch pressure
    p2 = TokenPipeline(cfg, PipelineParams(cc=1, p=1, pp=1))
    tput = p2.measure_throughput(n_batches=4)
    p2.close()
    assert tput > 0
