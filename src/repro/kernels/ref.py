"""Pure-jnp oracles for every Pallas kernel, and the default compute path of
the model zoo (kernels.ops dispatches here unless use_pallas=True).

  * ``attention_ref``      — causal (optionally sliding-window) SDPA
  * ``ssd_chunked_ref``    — Mamba2 state-space duality scan, chunked
  * ``rwkv6_chunked_ref``  — RWKV6 linear-attention recurrence, chunked
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------------- #
# attention
# --------------------------------------------------------------------- #
def attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                  q_offset: int = 0, logits_dtype=jnp.float32):
    """Grouped-query scaled-dot-product attention.

    q: (B, Sq, Hq, D); k, v: (B, Sk, Hkv, D) with Hq % Hkv == 0.
    ``q_offset`` is the absolute position of q[0] (decode: Sk - Sq).
    ``window`` > 0 enables sliding-window causal masking.
    Returns (B, Sq, Hq, D) in q.dtype.
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    g = Hq // Hkv
    qr = q.reshape(B, Sq, Hkv, g, D)
    scale = 1.0 / np.sqrt(D)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qr.astype(logits_dtype),
                        k.astype(logits_dtype)) * scale
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window > 0:
        mask &= kpos[None, :] > qpos[:, None] - window
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(B, Sq, Hq, D).astype(q.dtype)


def attention_blocked(q, k, v, *, causal: bool = True, window: int = 0,
                      q_offset: int = 0, bq: int = 1024, bk: int = 1024):
    """Flash-style attention in pure XLA: lax.scan over q blocks with an
    inner lax.scan over kv blocks carrying online-softmax statistics.

    Never materializes more than a (B, H, bq, bk) logits tile, so 32k-500k
    sequences lower with O(S) live memory.  Fully-masked tiles are still
    computed (the mask is applied numerically): the HLO FLOP count includes
    ~2x causal waste, which EXPERIMENTS.md §Roofline accounts for in the
    MODEL_FLOPS ratio.  Differentiable (both loops are scans).
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    g = Hq // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    nq, nk = Sq // bq, Sk // bk
    f32 = jnp.float32
    scale = 1.0 / np.sqrt(D)

    # (nq, B, bq, Hkv, g, D) blocks, head-major for clean einsums
    qb = q.reshape(B, nq, bq, Hkv, g, D)
    qb = jnp.moveaxis(qb, 1, 0).astype(f32) * scale
    kb = jnp.moveaxis(k.reshape(B, nk, bk, Hkv, D), 1, 0).astype(f32)
    vb = jnp.moveaxis(v.reshape(B, nk, bk, Hkv, D), 1, 0).astype(f32)

    kpos_base = jnp.arange(bk)
    qpos_base = jnp.arange(bq) + q_offset

    def q_block(carry, inp):
        qi, qblk = inp                                   # (), (B,bq,Hkv,g,D)
        qpos = qpos_base + qi * bq                       # (bq,)

        def kv_block(stats, kinp):
            m, l, acc = stats
            ki, kblk, vblk = kinp
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qblk, kblk)
            kpos = kpos_base + ki * bk
            mask = jnp.ones((bq, bk), bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window > 0:
                mask &= kpos[None, :] > qpos[:, None] - window
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + p.sum(-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vblk)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, g, bq), -1e30, f32)
        l0 = jnp.zeros((B, Hkv, g, bq), f32)
        a0 = jnp.zeros((B, Hkv, g, bq, D), f32)
        (m, l, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, a0), (jnp.arange(nk), kb, vb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]     # (B,Hkv,g,bq,D)
        return carry, jnp.moveaxis(out, 3, 1)            # (B,bq,Hkv,g,D)

    _, blocks = jax.lax.scan(q_block, (), (jnp.arange(nq), qb))
    out = jnp.moveaxis(blocks, 0, 1).reshape(B, Sq, Hq, D)
    return out.astype(q.dtype)


# --------------------------------------------------------------------- #
# Mamba2 SSD (state-space duality), chunked
# --------------------------------------------------------------------- #
def ssd_chunked_ref(x, dt, A, Bmat, Cmat, *, chunk: int = 256,
                    initial_state=None, return_state: bool = False):
    """Chunked SSD scan (Dao & Gu 2024, "minimal mamba2" algorithm).

    x:  (B, L, H, P)   inputs per head
    dt: (B, L, H)      positive step sizes (already softplus'd)
    A:  (H,)           negative per-head decay rates
    Bmat, Cmat: (B, L, N)  input/output projections (single group)
    Returns y: (B, L, H, P) and optionally final state (B, H, P, N).
    """
    Bsz, L, H, P = x.shape
    N = Bmat.shape[-1]
    if L % chunk:
        # pad with dt=0 steps: decay exp(0)=1, zero state update, outputs at
        # padded positions are discarded
        pad = chunk - L % chunk
        y = ssd_chunked_ref(
            jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))),
            jnp.pad(dt, ((0, 0), (0, pad), (0, 0))), A,
            jnp.pad(Bmat, ((0, 0), (0, pad), (0, 0))),
            jnp.pad(Cmat, ((0, 0), (0, pad), (0, 0))),
            chunk=chunk, initial_state=initial_state,
            return_state=return_state)
        if return_state:
            return y[0][:, :L], y[1]
        return y[:, :L]
    nc = L // chunk
    f32 = jnp.float32

    xc = x.reshape(Bsz, nc, chunk, H, P).astype(f32)
    dtc = dt.reshape(Bsz, nc, chunk, H).astype(f32)
    Bc = Bmat.reshape(Bsz, nc, chunk, N).astype(f32)
    Cc = Cmat.reshape(Bsz, nc, chunk, N).astype(f32)

    dA = dtc * A.astype(f32)[None, None, None, :]        # (B, nc, Q, H) <= 0
    dA_cum = jnp.cumsum(dA, axis=2)                      # within-chunk cumsum

    # intra-chunk (quadratic in chunk): causal decay matrix per head
    seg = dA_cum[:, :, :, None, :] - dA_cum[:, :, None, :, :]   # (B,nc,Q,Q,H)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    Ldec = jnp.where(causal[None, None, :, :, None], jnp.exp(seg), 0.0)
    cb = jnp.einsum("bcqn,bckn->bcqk", Cc, Bc)           # (B,nc,Q,Q)
    intra = jnp.einsum("bcqk,bcqkh,bckh,bckhp->bcqhp",
                       cb, Ldec, dtc, xc)

    # chunk-final states
    decay_to_end = jnp.exp(dA_cum[:, :, -1:, :] - dA_cum)        # (B,nc,Q,H)
    states = jnp.einsum("bckn,bckh,bckh,bckhp->bchpn",
                        Bc, decay_to_end, dtc, xc)               # (B,nc,H,P,N)

    # inter-chunk recurrence over chunk states
    chunk_decay = jnp.exp(dA_cum[:, :, -1, :])                   # (B,nc,H)
    s0 = (jnp.zeros((Bsz, H, P, N), f32) if initial_state is None
          else initial_state.astype(f32))

    def step(carry, inp):
        dec, st = inp                                            # (B,H), (B,H,P,N)
        new = carry * dec[..., None, None] + st
        return new, carry                                        # emit state *entering* chunk

    chunk_decay_t = jnp.moveaxis(chunk_decay, 1, 0)              # (nc,B,H)
    states_t = jnp.moveaxis(states, 1, 0)                        # (nc,B,H,P,N)
    final, entering = jax.lax.scan(step, s0, (chunk_decay_t, states_t))
    entering = jnp.moveaxis(entering, 0, 1)                      # (B,nc,H,P,N)

    # contribution of the entering state within each chunk
    state_decay = jnp.exp(dA_cum)                                # (B,nc,Q,H)
    inter = jnp.einsum("bcqn,bcqh,bchpn->bcqhp", Cc, state_decay, entering)

    y = (intra + inter).reshape(Bsz, L, H, P).astype(x.dtype)
    if return_state:
        return y, final
    return y


def ssd_decode_step(state, x_t, dt_t, A, B_t, C_t):
    """Single-token SSD recurrence.

    state: (B, H, P, N); x_t: (B, H, P); dt_t: (B, H); B_t, C_t: (B, N).
    Returns (y_t (B, H, P), new_state).
    """
    f32 = jnp.float32
    dA = jnp.exp(dt_t.astype(f32) * A.astype(f32)[None, :])      # (B, H)
    upd = jnp.einsum("bh,bhp,bn->bhpn", dt_t.astype(f32),
                     x_t.astype(f32), B_t.astype(f32))
    new_state = state * dA[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", new_state, C_t.astype(f32))
    return y.astype(x_t.dtype), new_state


# --------------------------------------------------------------------- #
# RWKV6 (Finch) linear attention with data-dependent decay, chunked
# --------------------------------------------------------------------- #
def rwkv6_chunked_ref(r, k, v, w, u, *, chunk: int = 128,
                      initial_state=None, return_state: bool = False):
    """Chunked RWKV6 WKV computation.

    r, k: (B, L, H, K); v: (B, L, H, V); w: (B, L, H, K) log-decay (<= 0,
    data-dependent); u: (H, K) bonus for the current token.
    State S: (B, H, K, V) with recurrence  S_t = diag(exp(w_t)) S_{t-1} + k_t v_t^T
    and output y_t = r_t @ (S_{t-1} + diag(u) k_t v_t^T).
    """
    B, L, H, K = r.shape
    V = v.shape[-1]
    if L % chunk:
        # pad with w=0 (decay 1), k=r=0: state unchanged, outputs discarded
        pad = chunk - L % chunk
        pad4 = ((0, 0), (0, pad), (0, 0), (0, 0))
        y = rwkv6_chunked_ref(
            jnp.pad(r, pad4), jnp.pad(k, pad4), jnp.pad(v, pad4),
            jnp.pad(w, pad4), u, chunk=chunk,
            initial_state=initial_state, return_state=return_state)
        if return_state:
            return y[0][:, :L], y[1]
        return y[:, :L]
    nc = L // chunk
    f32 = jnp.float32

    rc = r.reshape(B, nc, chunk, H, K).astype(f32)
    kc = k.reshape(B, nc, chunk, H, K).astype(f32)
    vc = v.reshape(B, nc, chunk, H, V).astype(f32)
    wc = w.reshape(B, nc, chunk, H, K).astype(f32)

    wcum = jnp.cumsum(wc, axis=2)                       # within-chunk log-decay
    # intra-chunk: y_t += sum_{s<t} r_t * exp(wcum_{t-1} - wcum_s) k_s v_s.
    # Split the decay exponent wcum_{t-1} - wcum_s (always <= 0) across the
    # two matmul operands; both factors stay bounded because the chunk is
    # short and |w| is clamped by the model (see models/rwkv.py).
    ri = rc * jnp.exp(wcum - wc)                        # exponent +wcum_{t-1}
    ki = kc * jnp.exp(-wcum)                            # exponent -wcum_s
    att = jnp.einsum("bcthk,bcshk->bchts", ri, ki)      # (B,nc,H,Q,Q)
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    att = jnp.where(strict[None, None, None], att, 0.0)
    intra = jnp.einsum("bchts,bcshv->bcthv", att, vc)
    # current-token bonus: u replaces the decay for s == t
    bonus = jnp.einsum("bcthk,bcthv->bcthv",
                       rc * u.astype(f32)[None, None, None] * kc, vc)

    # chunk summary: state update for the whole chunk
    total = wcum[:, :, -1:, :]                          # (B,nc,1,H,K)
    k_tail = kc * jnp.exp(total - wcum)                 # decay from s to end...
    # state contribution of chunk: sum_s exp(w_{s+1..Q}) k_s v_s
    chunk_state = jnp.einsum("bcshk,bcshv->bchkv", k_tail, vc)
    chunk_decay = jnp.exp(total[:, :, 0])               # (B,nc,H,K)

    s0 = (jnp.zeros((B, H, K, V), f32) if initial_state is None
          else initial_state.astype(f32))

    def step(carry, inp):
        dec, st = inp
        new = carry * dec[..., None] + st
        return new, carry

    final, entering = jax.lax.scan(
        step, s0, (jnp.moveaxis(chunk_decay, 1, 0),
                   jnp.moveaxis(chunk_state, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)             # (B,nc,H,K,V)

    inter = jnp.einsum("bcthk,bchkv->bcthv", ri, entering)
    y = (intra + inter + bonus).reshape(B, L, H, V).astype(r.dtype)
    if return_state:
        return y, final
    return y


def rwkv6_decode_step(state, r_t, k_t, v_t, w_t, u):
    """Single-token RWKV6 step.  state: (B,H,K,V); r,k,w: (B,H,K); v: (B,H,V)."""
    f32 = jnp.float32
    rt, kt, vt, wt = (a.astype(f32) for a in (r_t, k_t, v_t, w_t))
    kv = jnp.einsum("bhk,bhv->bhkv", kt, vt)
    y = jnp.einsum("bhk,bhkv->bhv", rt, state + u.astype(f32)[None, :, :, None] * kv)
    new_state = state * jnp.exp(wt)[..., None] + kv
    return y.astype(r_t.dtype), new_state


def rwkv6_sequential_ref(r, k, v, w, u, initial_state=None):
    """Token-by-token oracle used to validate the chunked form."""
    B, L, H, K = r.shape
    V = v.shape[-1]
    state = (jnp.zeros((B, H, K, V), jnp.float32) if initial_state is None
             else initial_state.astype(jnp.float32))
    ys = []
    for t in range(L):
        y, state = rwkv6_decode_step(state, r[:, t], k[:, t], v[:, t],
                                     w[:, t], u)
        ys.append(y)
    return jnp.stack(ys, axis=1), state


# --------------------------------------------------------------------- #
# batched transfer-surface selection (fleet tuner)
# --------------------------------------------------------------------- #
def batched_predict_argmax_ref(values, idx):
    """Score candidate points on stacked surface grids and pick the best.

    values: (S, G) flattened integer-lattice surface values; idx: (B, P)
    flat candidate indices.  Returns (best (B, S) f32, argk (B, S) int32):
    the best candidate's value and its position in the candidate list, for
    every request x surface pair.  Oracle for the Pallas one-hot-matmul
    kernel in ``kernels.transfer_select``.
    """
    values = jnp.asarray(values, jnp.float32)
    idx = jnp.asarray(idx, jnp.int32)
    S = values.shape[0]
    B, P = idx.shape
    scores = jnp.take(values, idx.reshape(-1), axis=1).reshape(S, B, P)
    scores = jnp.moveaxis(scores, 0, 1)                  # (B, S, P)
    return jnp.max(scores, axis=-1), jnp.argmax(scores, axis=-1).astype(jnp.int32)


# --------------------------------------------------------------------- #
# batched natural-cubic-spline fit (offline refresh hot path)
# --------------------------------------------------------------------- #
@jax.jit
def nat_spline_fit_ref(x, Y):
    """Natural-cubic-spline coefficients for many rows via a Thomas solve.

    x: (N,) strictly increasing knots; Y: (R, N) values.  Returns
    (R, N-1, 4) local coefficients a + b t + c t^2 + d t^3 — the jnp twin of
    ``repro.core.spline.nat_spline_coeffs``.  The tridiagonal system for the
    interior second derivatives is shared across rows, so the Thomas
    forward-elimination factors are computed once from ``x`` while the
    per-row substitution sweeps run vectorized over all R rows inside
    ``lax.scan`` (the "vmapped Thomas" refit of the continuous-refresh
    subsystem).  Oracle for the Pallas kernel in ``kernels.spline_fit``.
    """
    dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    x = jnp.asarray(x, dtype)
    Y = jnp.atleast_2d(jnp.asarray(Y, dtype))
    R, n = Y.shape
    if n == 1:
        return jnp.concatenate([Y[:, :, None], jnp.zeros((R, 1, 3), dtype)],
                               axis=-1)
    if n == 2:
        slope = (Y[:, 1] - Y[:, 0]) / (x[1] - x[0])
        zero = jnp.zeros((R,), dtype)
        return jnp.stack([Y[:, 0], slope, zero, zero], axis=-1)[:, None, :]
    h = jnp.diff(x)                                      # (N-1,)
    # interior system over M_1..M_{n-2}; natural boundary M_0 = M_{n-1} = 0
    sub = h[:-1]                                         # (m,) a_j, a_0 unused
    diag = 2.0 * (h[:-1] + h[1:])                        # (m,)
    sup = h[1:]                                          # (m,) c_{m-1} unused
    rhs = 6.0 * ((Y[:, 2:] - Y[:, 1:-1]) / h[1:]
                 - (Y[:, 1:-1] - Y[:, :-2]) / h[:-1])    # (R, m)

    def fwd(carry, inp):
        cp_prev, dp_prev = carry
        a_j, b_j, c_j, d_j = inp                         # d_j: (R,)
        denom = b_j - a_j * cp_prev
        cp = c_j / denom
        dp = (d_j - a_j * dp_prev) / denom
        return (cp, dp), (cp, dp)

    cp0 = sup[0] / diag[0]
    dp0 = rhs[:, 0] / diag[0]
    _, (cps, dps) = jax.lax.scan(
        fwd, (cp0, dp0),
        (sub[1:], diag[1:], sup[1:], jnp.moveaxis(rhs[:, 1:], 1, 0)))
    cps = jnp.concatenate([cp0[None], cps])              # (m,)
    dps = jnp.concatenate([dp0[None, :], dps])           # (m, R)

    def bwd(m_next, inp):
        cp_j, dp_j = inp
        m_j = dp_j - cp_j * m_next
        return m_j, m_j

    _, interior = jax.lax.scan(bwd, dps[-1], (cps[:-1], dps[:-1]),
                               reverse=True)
    interior = jnp.concatenate([interior, dps[-1:]], axis=0)  # (m, R)
    zeros = jnp.zeros((1, R), dtype)
    M = jnp.moveaxis(jnp.concatenate([zeros, interior, zeros]), 1, 0)  # (R, N)
    a = Y[:, :-1]
    b = (Y[:, 1:] - Y[:, :-1]) / h - h * (2.0 * M[:, :-1] + M[:, 1:]) / 6.0
    c = M[:, :-1] / 2.0
    d = (M[:, 1:] - M[:, :-1]) / (6.0 * h)
    return jnp.stack([a, b, c, d], axis=-1)


# --------------------------------------------------------------------- #
# batched nearest-centroid assignment (offline clustering hot loop)
# --------------------------------------------------------------------- #
@jax.jit
def cluster_assign_ref(X, C):
    """Nearest-centroid assignment for many points at once.

    X: (N, d) points; C: (M, d) centroids.  Returns (labels (N,) int32,
    min squared distance (N,) f32).  The squared distances are expanded as
    ``|x|^2 - 2 x.c + |c|^2`` so the hot loop is one (N, d) x (d, M) matmul
    instead of an (N, M, d) broadcast — the formulation the Pallas kernel in
    ``kernels.cluster_assign`` tiles over N blocks on the MXU.  Oracle for
    that kernel and the default compute path off-TPU (see ``kernels.ops``).
    """
    X = jnp.asarray(X, jnp.float32)
    C = jnp.asarray(C, jnp.float32)
    x2 = (X * X).sum(-1, keepdims=True)                  # (N, 1)
    c2 = (C * C).sum(-1)[None, :]                        # (1, M)
    xc = jnp.dot(X, C.T, precision=jax.lax.Precision.HIGHEST)
    d2 = jnp.maximum(x2 - 2.0 * xc + c2, 0.0)            # (N, M)
    return jnp.argmin(d2, axis=1).astype(jnp.int32), jnp.min(d2, axis=1)


def ssd_sequential_ref(x, dt, A, Bmat, Cmat, initial_state=None):
    """Token-by-token SSD oracle used to validate the chunked form."""
    Bsz, L, H, P = x.shape
    N = Bmat.shape[-1]
    state = (jnp.zeros((Bsz, H, P, N), jnp.float32) if initial_state is None
             else initial_state.astype(jnp.float32))
    ys = []
    for t in range(L):
        y, state = ssd_decode_step(state, x[:, t], dt[:, t], A,
                                   Bmat[:, t], Cmat[:, t])
        ys.append(y)
    return jnp.stack(ys, axis=1), state
