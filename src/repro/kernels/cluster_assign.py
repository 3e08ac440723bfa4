"""Tiled nearest-centroid assignment for TPU (Pallas): distance + argmin.

The offline clustering subsystem's hot loop at million-entry scale is
assigning every historical log row to its nearest cluster centroid — an
``(N, d) x (M, d)`` pairwise squared-distance followed by an argmin over the
small centroid axis (see ``core.clustering``).  The kernel tiles the point
set over N blocks; each grid step holds one ``(NB, d)`` point tile and the
whole (tiny) centroid matrix in VMEM, expands the squared distance as
``|x|^2 - 2 x.c + |c|^2`` so the cross term is a single MXU matmul, and
reduces to per-point label + distance columns in VMEM.  The XLA oracle is
``kernels.ref.cluster_assign_ref`` and is the default compute path off-TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _assign_kernel(x_ref, c_ref, lab_ref, dist_ref):
    x = x_ref[...].astype(jnp.float32)  # (NB, d)
    c = c_ref[...].astype(jnp.float32)  # (M, d)
    x2 = (x * x).sum(axis=1, keepdims=True)  # (NB, 1)
    c2 = (c * c).sum(axis=1)[None, :]  # (1, M)
    # HIGHEST: at the default the MXU rounds f32 operands to bf16, which
    # moves labels off float64 Lloyd's (see core.clustering)
    xc = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST
    )  # (NB, M) on MXU
    d2 = jnp.maximum(x2 - 2.0 * xc + c2, 0.0)
    lab_ref[...] = jnp.argmin(d2, axis=1).astype(jnp.int32)[:, None]
    dist_ref[...] = jnp.min(d2, axis=1)[:, None]


@functools.partial(jax.jit, static_argnames=("nb", "interpret"))
def cluster_assign_pallas(X, C, *, nb: int = 1024, interpret: bool = False):
    """X (N, d) points, C (M, d) centroids -> (labels (N,) i32, d2 (N,) f32).

    One grid step per ``nb``-point block; the centroid matrix rides along in
    VMEM since M and d are tiny (M <= 16 model orders, d = 4 log features),
    so the VMEM working set is ``nb * (d + M + 2) * 4`` bytes (~100 KB at
    nb=1024).  N is padded up to a block multiple and sliced back.
    """
    X = jnp.asarray(X, jnp.float32)
    C = jnp.asarray(C, jnp.float32)
    n, d = X.shape
    m = C.shape[0]
    nb = min(nb, n)
    pad = (-n) % nb
    if pad:
        X = jnp.concatenate([X, jnp.zeros((pad, d), X.dtype)], axis=0)
    lab, dist = pl.pallas_call(
        _assign_kernel,
        grid=((n + pad) // nb,),
        in_specs=[
            pl.BlockSpec((nb, d), lambda i: (i, 0)),
            pl.BlockSpec((m, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((nb, 1), lambda i: (i, 0)),
            pl.BlockSpec((nb, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n + pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((n + pad, 1), jnp.float32),
        ],
        interpret=interpret,
    )(X, C)
    return lab[:n, 0], dist[:n, 0]
