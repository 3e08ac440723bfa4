"""Batched transfer-surface selection for TPU (Pallas): one-hot matmul.

Scoring B requests x S surfaces x P candidate points is a gather from the
stacked integer-lattice surface tensors (see ``core.batched``).  TPUs dislike
scatters and gathers but love matmuls, so the kernel walks the P candidate
columns of each request block: column p becomes a one-hot ``(BB, G)`` tile,
one MXU contraction with the ``(S, G)`` value stack scores it for every
surface, and a running max/argmax over the columns keeps the best candidate
per (request, surface) pair in VMEM.  Candidate indices stay in lanes and
are only ever broadcast along them, never reshaped into sublanes, which
Mosaic cannot lay out.  The contraction runs at ``Precision.HIGHEST``: a
one-hot row times the stack then sums one f32 value with exact zeros, so the
kernel returns surface values bit-exactly.  The XLA oracle lives in
``kernels.ref.batched_predict_argmax_ref`` and is the default compute path
off-TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _select_kernel(idx_ref, val_ref, best_ref, argk_ref, *, bb, n_cand, n_grid):
    vals = val_ref[...].astype(jnp.float32)  # (S, G)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bb, n_grid), 1)

    def score(p):  # candidate column p on every surface -> (BB, S)
        onehot = (cols == idx_ref[:, p : p + 1]).astype(jnp.float32)  # (BB, G)
        return jax.lax.dot_general(
            onehot,
            vals,
            (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    best = score(0)
    argk = jnp.zeros(best.shape, jnp.int32)
    for p in range(1, n_cand):  # static unroll; P is a handful of candidates
        s = score(p)
        better = s > best  # strict: the first maximal candidate wins, as in argmax
        best = jnp.where(better, s, best)
        argk = jnp.where(better, p, argk)
    best_ref[...] = best
    argk_ref[...] = argk


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def batched_predict_argmax_pallas(values, idx, *, bb: int = 8, interpret: bool = False):
    """values (S, G) f32, idx (B, P) int32 -> (best (B, S), argk (B, S)).

    One grid step per ``bb``-request block; one candidate column's one-hot
    tile is ``bb * G * 4`` bytes (128 KB at bb=8, G=4096), and the whole
    value stack rides along in VMEM since S is small.
    """
    values = jnp.asarray(values, jnp.float32)
    idx = jnp.asarray(idx, jnp.int32)
    S, G = values.shape
    B, P = idx.shape
    bb = min(bb, B)
    pad = (-B) % bb
    if pad:
        idx = jnp.concatenate([idx, jnp.zeros((pad, P), idx.dtype)], axis=0)
    kernel = functools.partial(_select_kernel, bb=bb, n_cand=P, n_grid=G)
    best, argk = pl.pallas_call(
        kernel,
        grid=((B + pad) // bb,),
        in_specs=[
            pl.BlockSpec((bb, P), lambda b: (b, 0)),
            pl.BlockSpec((S, G), lambda b: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, S), lambda b: (b, 0)),
            pl.BlockSpec((bb, S), lambda b: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B + pad, S), jnp.float32),
            jax.ShapeDtypeStruct((B + pad, S), jnp.int32),
        ],
        interpret=interpret,
    )(idx, values)
    return best[:B], argk[:B]
