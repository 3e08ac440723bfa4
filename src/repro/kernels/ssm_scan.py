"""Mamba2 SSD scan for TPU (Pallas).

Grid layout: (batch, n_chunks) with the chunk dimension sequential; the
running SSM state (H, P, N) lives in a VMEM scratch buffer that persists
across chunk steps (re-initialized when the batch index advances).  Each
grid step computes the intra-chunk quadratic term, the inter-chunk state
contribution, and the state update — the same math as the XLA reference
``ssd_chunked_ref`` but fused into one VMEM-resident kernel per chunk.

VMEM working set per step (zamba2-7b: H=112, P=64, N=64, Q=128):
state 1.8 MB + x/out chunks 2x1.8 MB + decay tile (Q, Q, H) in f32 streamed
per-head-block — block sizes keep it under ~8 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, o_ref, state_scr, *,
                chunk: int, has_init: bool):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _reset():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)          # (Q, H, P)
    dt = dt_ref[0].astype(jnp.float32)        # (Q, H)
    A = a_ref[...].astype(jnp.float32)        # (H,)
    Bm = b_ref[0].astype(jnp.float32)         # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)         # (Q, N)

    dA = dt * A[None, :]                      # (Q, H), <= 0
    dA_cum = jnp.cumsum(dA, axis=0)

    # intra-chunk
    seg = dA_cum[:, None, :] - dA_cum[None, :, :]           # (Q, Q, H)
    causal = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))
    Ldec = jnp.where(causal[:, :, None], jnp.exp(seg), 0.0)
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))   # (Q, Q)
    intra = jnp.einsum("qk,qkh,kh,khp->qhp", cb, Ldec, dt, x)

    # inter-chunk: contribution of the entering state
    state = state_scr[...]                                   # (H, P, N)
    state_decay = jnp.exp(dA_cum)                            # (Q, H)
    inter = jnp.einsum("qn,qh,hpn->qhp", Cm, state_decay, state)

    o_ref[0, ...] = (intra + inter).astype(o_ref.dtype)

    # state update
    decay_to_end = jnp.exp(dA_cum[-1:, :] - dA_cum)          # (Q, H)
    upd = jnp.einsum("qn,qh,qh,qhp->hpn", Bm, decay_to_end, dt, x)
    chunk_decay = jnp.exp(dA_cum[-1, :])                     # (H,)
    state_scr[...] = state * chunk_decay[:, None, None] + upd


@functools.partial(jax.jit, static_argnames=("chunk", "return_state",
                                             "interpret"))
def ssd_pallas(x, dt, A, B, C, *, chunk: int = 128, initial_state=None,
               return_state: bool = False, interpret: bool = False):
    """x: (B, L, H, P); dt: (B, L, H); A: (H,); B, C: (B, L, N)."""
    Bsz, L, H, P = x.shape
    N = B.shape[-1]
    assert initial_state is None, "initial_state handled by the XLA path"
    if L % chunk:
        pad = chunk - L % chunk
        out = ssd_pallas(jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))),
                         jnp.pad(dt, ((0, 0), (0, pad), (0, 0))), A,
                         jnp.pad(B, ((0, 0), (0, pad), (0, 0))),
                         jnp.pad(C, ((0, 0), (0, pad), (0, 0))),
                         chunk=chunk, return_state=return_state,
                         interpret=interpret)
        if return_state:
            raise NotImplementedError("padded + return_state unsupported")
        return out[:, :L]
    nc = L // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk, has_init=False)
    out = pl.pallas_call(
        kernel,
        grid=(Bsz, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, H, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, chunk, H), lambda b, c: (b, c, 0)),
            pl.BlockSpec((H,), lambda b, c: (0,)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, H, P), lambda b, c: (b, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz, L, H, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((H, P, N), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(x, dt, A, B, C)
    if return_state:
        # final state comes from the XLA path when needed (prefill)
        from repro.kernels.ref import ssd_chunked_ref
        _, fin = ssd_chunked_ref(x, dt, A, B, C, chunk=chunk,
                                 return_state=True)
        return out, fin
    return out
