"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

Layers are stacked (as everywhere in this repo), split into per-stage
sub-stacks, and scheduled round-robin over microbatches: at tick ``t`` stage
``s`` runs microbatch ``t - s`` and hands its activation to stage ``s + 1``
via ``collective-permute``.  ``M + S - 1`` ticks drain ``M`` microbatches
through ``S`` stages; the first/last ``S - 1`` ticks are the bubble.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    n_stages: int
    n_microbatches: int

    @property
    def bubble_fraction(self) -> float:
        """Idle fraction of the GPipe schedule: (S-1) / (M+S-1)."""
        s, m = self.n_stages, self.n_microbatches
        return (s - 1) / (m + s - 1)


def split_stages(params, n_stages: int):
    """Reshape layer-stacked leaves (L, ...) -> (S, L/S, ...)."""
    def split(a):
        n_layers = a.shape[0]
        assert n_layers % n_stages == 0, \
            f"{n_layers} layers not divisible into {n_stages} stages"
        return a.reshape((n_stages, n_layers // n_stages) + a.shape[1:])
    return jax.tree.map(split, params)


def make_pipeline_fn(layer_slice, mesh, pcfg: PipelineConfig):
    """Build fn(stage_params, xs) running ``layer_slice`` as a pipeline.

    ``layer_slice(params, x)`` applies one stage's layer sub-stack (leaves
    shaped (L/S, ...)) to a microbatch ``x``.  ``stage_params`` comes from
    :func:`split_stages`; ``xs`` is (n_microbatches, microbatch, ...).
    Output matches ``xs``'s shape and equals sequential application of the
    full stack to every microbatch.
    """
    n_stages, n_micro = pcfg.n_stages, pcfg.n_microbatches
    assert mesh.shape["stage"] == n_stages, (mesh.shape, n_stages)
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def per_stage(stage_params, xs):
        params = jax.tree.map(lambda a: a[0], stage_params)  # my slice
        s = lax.axis_index("stage")

        def tick(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t (stale re-reads during the drain
            # ticks flow through but are never recorded as output)
            feed = lax.dynamic_index_in_dim(
                xs, jnp.minimum(t, n_micro - 1), 0, keepdims=False)
            state = jnp.where(s == 0, feed, state)
            state = layer_slice(params, state)
            out_idx = t - (n_stages - 1)   # last stage just finished out_idx
            written = lax.dynamic_update_index_in_dim(
                outputs, state, jnp.maximum(out_idx, 0), 0)
            outputs = jnp.where(out_idx >= 0, written, outputs)
            state = lax.ppermute(state, "stage", perm)
            return (state, outputs), None

        # scan over ticks keeps the program size constant in n_micro
        init = (jnp.zeros(xs.shape[1:], xs.dtype), jnp.zeros_like(xs))
        (_, outputs), _ = lax.scan(
            tick, init, jnp.arange(n_micro + n_stages - 1))
        # every stage wrote its own (mostly garbage) buffer; keep the last
        # stage's and replicate it
        keep = jnp.where(s == n_stages - 1, outputs, jnp.zeros_like(outputs))
        return lax.psum(keep, "stage")

    return shard_map(per_stage, mesh=mesh, in_specs=(P("stage"), P()),
                     out_specs=P(), check_vma=False)
