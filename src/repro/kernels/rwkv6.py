"""RWKV6 WKV scan for TPU (Pallas).

Grid layout: (batch, heads, n_chunks); the chunk dimension is sequential and
the per-(batch, head) running state (K, V) persists in VMEM scratch.  Each
step computes the intra-chunk lower-triangular term, the current-token bonus,
the inter-chunk contribution from the entering state, and the state update —
matching ``rwkv6_chunked_ref`` tile for tile.

VMEM per step is tiny (state 64x64 f32 = 16 KB, chunk tiles Q=16) — the
kernel trades VMEM pressure for grid parallelism over (batch, heads).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state_scr, *,
                 chunk: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _reset():
        state_scr[...] = jnp.zeros_like(state_scr)

    r = r_ref[0, :, 0, :].astype(jnp.float32)      # (Q, K)
    k = k_ref[0, :, 0, :].astype(jnp.float32)
    v = v_ref[0, :, 0, :].astype(jnp.float32)      # (Q, V)
    w = w_ref[0, :, 0, :].astype(jnp.float32)      # (Q, K), <= 0
    u = u_ref[0].astype(jnp.float32)               # (K,)

    wcum = jnp.cumsum(w, axis=0)
    ri = r * jnp.exp(wcum - w)                     # exponent +wcum_{t-1}
    ki = k * jnp.exp(-wcum)                        # exponent -wcum_s
    att = jax.lax.dot_general(ri, ki, (((1,), (1,)), ((), ())))   # (Q, Q)
    strict = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_), -1)
    att = jnp.where(strict, att, 0.0)
    intra = jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())))
    bonus = jnp.einsum("qk,qk,qv->qv", r * u[None, :], k, v)

    state = state_scr[...]                         # (K, V)
    inter = jax.lax.dot_general(ri, state, (((1,), (0,)), ((), ())))

    o_ref[0, :, 0, :] = (intra + inter + bonus).astype(o_ref.dtype)

    total = wcum[-1:, :]                           # (1, K)
    k_tail = k * jnp.exp(total - wcum)             # decay s -> chunk end
    new = jax.lax.dot_general(k_tail, v, (((0,), (0,)), ((), ())))  # (K, V)
    state_scr[...] = state * jnp.exp(total[0])[:, None] + new


@functools.partial(jax.jit, static_argnames=("chunk", "return_state",
                                             "interpret"))
def rwkv6_pallas(r, k, v, w, u, *, chunk: int = 16, initial_state=None,
                 return_state: bool = False, interpret: bool = False):
    """r/k/w: (B, L, H, K); v: (B, L, H, V); u: (H, K)."""
    B, L, H, K = r.shape
    V = v.shape[-1]
    assert initial_state is None, "initial_state handled by the XLA path"
    if L % chunk:
        pad = chunk - L % chunk
        pad4 = ((0, 0), (0, pad), (0, 0), (0, 0))
        out = rwkv6_pallas(jnp.pad(r, pad4), jnp.pad(k, pad4),
                           jnp.pad(v, pad4), jnp.pad(w, pad4), u,
                           chunk=chunk, return_state=return_state,
                           interpret=interpret)
        if return_state:
            raise NotImplementedError("padded + return_state unsupported")
        return out[:, :L]
    nc = L // chunk

    kernel = functools.partial(_rwkv_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, K), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1, K), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1, V), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1, K), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, K), lambda b, h, c: (h, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, V), lambda b, h, c: (b, c, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, L, H, V), r.dtype),
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
    )(r, k, v, w, u)
    if return_state:
        from repro.kernels.ref import rwkv6_chunked_ref
        _, fin = rwkv6_chunked_ref(r, k, v, w, u, chunk=chunk,
                                   return_state=True)
        return out, fin
    return out
