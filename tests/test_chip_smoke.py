"""``chip_smoke.py`` and the compile-cache helper it shares with the
benchmarks, exercised on the CPU.

The smoke itself refuses to run without a TPU; its phases are driven here
at a small size with the three transfer kernels switched to interpret
mode, so a change that breaks an entry point the smoke calls fails here
before it costs chip time.
"""
import dataclasses
import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolve their module
    spec.loader.exec_module(mod)
    yield mod
    del sys.modules["chip_smoke"]


@pytest.fixture
def interpret_kernels(monkeypatch):
    """Route every transfer kernel through the Pallas interpreter."""
    import repro.kernels.cluster_assign as ca
    import repro.kernels.spline_fit as sf
    import repro.kernels.transfer_select as ts

    for mod, name in ((ca, "cluster_assign_pallas"),
                      (sf, "nat_spline_fit_pallas"),
                      (ts, "batched_predict_argmax_pallas")):
        kernel = getattr(mod, name)
        monkeypatch.setattr(
            mod, name,
            lambda *a, _k=kernel, interpret=False, **kw:
                _k(*a, interpret=True, **kw))


def _run(smoke, name, fn):
    results = []
    value = smoke.run_phase(name, fn, None, results)
    assert results == [(name, True)], name
    return value


def test_refuses_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, SMOKE], capture_output=True,
                          text=True, env=env, cwd=tmp_path, timeout=120)
    assert proc.returncode != 0
    assert "no TPU device found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_one_chip_phases_pass_at_small_size(smoke, interpret_kernels,
                                            capsys):
    sz = dataclasses.replace(
        smoke.FULL, discovery_rows=20_000, history_days=4.0,
        refit_entries=520, fleet_sessions=200, select_requests=24,
        service_queries=200, service_ingest=520)
    _run(smoke, "discovery_fit_clusters",
         lambda: smoke.phase_fit_clusters(sz))
    db, fresh = _run(smoke, "discovery_offline_analysis",
                     lambda: smoke.phase_offline_analysis(sz))
    _run(smoke, "refit", lambda: smoke.phase_refit(sz, db, fresh))
    _run(smoke, "fleet", lambda: smoke.phase_fleet(sz, db))
    _run(smoke, "batched_select", lambda: smoke.phase_select(sz, db))
    _run(smoke, "service", lambda: smoke.phase_service(sz, db, fresh))
    assert '"pass_kernel_path": true' in capsys.readouterr().out


def test_collective_phase_passes_on_four_host_devices(smoke):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs the suite's four host devices")
    sz = dataclasses.replace(smoke.FULL, grad_elems=65_536)
    _run(smoke, "collectives", lambda: smoke.phase_collectives(sz, 4))


def test_all_reduce_group_sizes_reads_both_hlo_spellings(smoke):
    hlo = "\n".join([
        "%a = f32[8] all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%r",
        "%b = f32[8] all-reduce-start(%y), replica_groups=[1,4]<=[4]",
        "%c = f32[8] all-reduce(%z), replica_groups={{0},{1}}, to_apply=%r",
        "%d = f32[8] add(%a, %b)",
    ])
    assert smoke.all_reduce_group_sizes(hlo) == [4, 4, 1]


_CACHE_PROBE = """
import jax, jax.numpy as jnp
import repro.core, repro.kernels.ops
from repro.launch import compile_cache
untouched = jax.config.jax_compilation_cache_dir is None
path = compile_cache.enable_compile_cache()
print(untouched, path, jax.config.jax_compilation_cache_dir, sep="\\n")
if {compile}:
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(5)).block_until_ready()
"""


def _cache_probe(env, compile_one: bool):
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(compile=compile_one)],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.split("\n")[:3]


def test_compile_cache_goes_where_the_environment_says(jax_subprocess_env,
                                                       tmp_path):
    env = dict(jax_subprocess_env, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    untouched, path, configured = _cache_probe(env, compile_one=True)
    assert path == configured == str(tmp_path / "cache")
    assert any(f.startswith("jit_") for f in os.listdir(path))


def test_compile_cache_defaults_to_one_fixed_path_in_the_checkout(
        jax_subprocess_env):
    env = dict(jax_subprocess_env, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    first = _cache_probe(env, compile_one=False)
    second = _cache_probe(env, compile_one=False)
    untouched, path, configured = first
    assert untouched == "True"  # importing repro set no cache directory
    assert first == second
    assert path == configured == os.path.join(REPO, ".jax_cache")
