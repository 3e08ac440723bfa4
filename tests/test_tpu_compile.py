"""The tuner's kernels and sweeps compile for a TPU v5e at their real sizes.

Nothing runs: the TPU compiler compiles for a described ``v5e:2x2`` chip,
which refuses what interpret mode accepts (lane layouts Mosaic cannot
build, fast memory a kernel may not use).  The topology is described in a
fixture, never while a module is imported, and the persistent compilation
cache is off around these compiles: an entry compiled for a described chip
cannot be read back without one.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _assign(spec):
    from repro.kernels.cluster_assign import cluster_assign_pallas

    return cluster_assign_pallas, [spec((1_000_000, 4)), spec((12, 4))]


def _spline(spec):
    from repro.kernels.spline_fit import nat_spline_fit_pallas

    return nat_spline_fit_pallas, [spec((16,)), spec((512, 16))]


def _select(spec):
    from repro.kernels.transfer_select import batched_predict_argmax_pallas

    return batched_predict_argmax_pallas, [spec((8, 4096)),
                                           spec((256, 16), jnp.int32)]


def _minibatch_sweep(spec):
    from repro.core.clustering import _jax_sweeps

    sweep, _ = _jax_sweeps()
    return sweep, [spec((1_000_000, 4)), spec((9, 12, 4)),
                   spec((80, 2048), jnp.int32)]


def _refine_and_stats(spec):
    from repro.core.clustering import _CHUNK, _jax_sweeps

    _, refine = _jax_sweeps()
    nc = -(-1_000_000 // _CHUNK)
    return refine, [spec((nc, _CHUNK, 4)), spec((nc, _CHUNK)),
                    spec((9, 12, 4)), spec((5,))]


# (case, shapes and function to compile, a Pallas kernel is expected)
CASES = [
    ("cluster_assign_1e6x12", _assign, True),
    ("spline_fit_512x16", _spline, True),
    ("transfer_select_B256_P16", _select, True),
    ("minibatch_sweep_1e6", _minibatch_sweep, False),
    ("refine_and_stats_1e6", _refine_and_stats, False),
]


@pytest.mark.parametrize("case,build,kernel", CASES,
                         ids=[c[0] for c in CASES])
def test_compiles_for_v5e(one_chip, case, build, kernel):
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = build(spec)
    compiled = jax.jit(fn).lower(*args).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == kernel, case
