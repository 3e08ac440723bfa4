"""Pallas kernels vs. pure-jnp oracles (interpret mode), with shape/dtype
sweeps, plus chunked-vs-sequential oracle equivalence."""
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssm_scan import ssd_pallas
from repro.kernels.rwkv6 import rwkv6_pallas
from repro.kernels import ref

RNG = np.random.default_rng(0)


def _rand(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


# ----------------------------- attention ----------------------------- #
ATTN_CASES = [
    # (B, Sq, Sk, Hq, Hkv, D, causal, window, dtype)
    (1, 128, 128, 4, 2, 64, True, 0, jnp.float32),
    (2, 256, 256, 4, 4, 128, True, 0, jnp.float32),
    (1, 128, 128, 8, 2, 64, True, 64, jnp.float32),
    (1, 128, 256, 4, 2, 64, False, 0, jnp.float32),
    (1, 256, 256, 2, 1, 128, True, 128, jnp.float32),
    (2, 128, 128, 4, 2, 64, True, 0, jnp.bfloat16),
]


@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_matches_oracle(case):
    B, Sq, Sk, Hq, Hkv, D, causal, window, dtype = case
    q = _rand((B, Sq, Hq, D), dtype)
    k = _rand((B, Sk, Hkv, D), dtype)
    v = _rand((B, Sk, Hkv, D), dtype)
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 q_offset=Sk - Sq, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window,
                             q_offset=Sk - Sq)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_block_size_invariance():
    q = _rand((1, 256, 4, 64))
    k = _rand((1, 256, 2, 64))
    v = _rand((1, 256, 2, 64))
    a = flash_attention_pallas(q, k, v, bq=128, bk=128, interpret=True)
    b = flash_attention_pallas(q, k, v, bq=64, bk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                               rtol=2e-5)


# ----------------------------- SSD (Mamba2) --------------------------- #
SSD_CASES = [
    # (B, L, H, P, N, chunk, dtype)
    (2, 64, 4, 8, 16, 16, jnp.float32),
    (1, 128, 2, 16, 32, 32, jnp.float32),
    (1, 96, 3, 8, 8, 32, jnp.float32),      # padded path (96 % 32 == 0) -> exact
    (2, 80, 2, 8, 16, 32, jnp.float32),     # 80 % 32 != 0 -> padding branch
    (1, 64, 4, 8, 16, 16, jnp.bfloat16),
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_pallas_matches_oracle(case):
    B, L, H, P, N, chunk, dtype = case
    x = _rand((B, L, H, P), dtype)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (B, L, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, (H,)), jnp.float32)
    Bm = _rand((B, L, N))
    Cm = _rand((B, L, N))
    got = ssd_pallas(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    want = ref.ssd_chunked_ref(x, dt, A, Bm, Cm, chunk=chunk)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@given(st.integers(1, 3), st.integers(1, 4), st.integers(0, 1000))
@settings(max_examples=8, deadline=None)
def test_ssd_chunked_matches_sequential(B, H, seed):
    rng = np.random.default_rng(seed)
    L, P, N = 48, 4, 8
    x = jnp.asarray(rng.normal(size=(B, L, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.3, (B, L, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.2, 2.0, (H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, L, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, L, N)), jnp.float32)
    y1, s1 = ref.ssd_chunked_ref(x, dt, A, Bm, Cm, chunk=16,
                                 return_state=True)
    y2, s2 = ref.ssd_sequential_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4,
                               rtol=1e-3)


# ------------------------------- RWKV6 -------------------------------- #
RWKV_CASES = [
    # (B, L, H, K, V, chunk, dtype)
    (2, 64, 4, 8, 8, 16, jnp.float32),
    (1, 128, 2, 16, 16, 16, jnp.float32),
    (2, 72, 2, 8, 8, 16, jnp.float32),      # padding branch
    (1, 64, 4, 8, 8, 16, jnp.bfloat16),
]


@pytest.mark.parametrize("case", RWKV_CASES)
def test_rwkv6_pallas_matches_oracle(case):
    B, L, H, K, V, chunk, dtype = case
    r = _rand((B, L, H, K), dtype)
    k = _rand((B, L, H, K), dtype)
    v = _rand((B, L, H, V), dtype)
    w = jnp.asarray(-RNG.uniform(0.01, 3.0, (B, L, H, K)), jnp.float32)
    u = _rand((H, K))
    got = rwkv6_pallas(r, k, v, w, u, chunk=chunk, interpret=True)
    want = ref.rwkv6_chunked_ref(r, k, v, w, u, chunk=chunk)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@given(st.integers(1, 2), st.integers(1, 3), st.integers(0, 1000))
@settings(max_examples=8, deadline=None)
def test_rwkv6_chunked_matches_sequential(B, H, seed):
    rng = np.random.default_rng(seed)
    L, K, V = 48, 8, 8
    r = jnp.asarray(rng.normal(size=(B, L, H, K)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, L, H, K)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, L, H, V)), jnp.float32)
    w = jnp.asarray(-rng.uniform(0.01, 3.5, (B, L, H, K)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(H, K)), jnp.float32)
    y1, s1 = ref.rwkv6_chunked_ref(r, k, v, w, u, chunk=16, return_state=True)
    y2, s2 = ref.rwkv6_sequential_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=5e-4,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=5e-4,
                               rtol=1e-3)


# ----------------------- decode-step consistency ---------------------- #
def test_ssd_decode_step_matches_scan_tail():
    B, L, H, P, N = 1, 33, 2, 4, 8
    x = _rand((B, L, H, P))
    dt = jnp.asarray(RNG.uniform(0.05, 0.2, (B, L, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 1.0, (H,)), jnp.float32)
    Bm = _rand((B, L, N))
    Cm = _rand((B, L, N))
    y_all, state = ref.ssd_chunked_ref(x, dt, A, Bm, Cm, chunk=16,
                                       return_state=True)
    # replay the last token from the state after L-1 tokens
    _, state_prev = ref.ssd_chunked_ref(x[:, :-1], dt[:, :-1], A,
                                        Bm[:, :-1], Cm[:, :-1], chunk=16,
                                        return_state=True)
    y_t, state_t = ref.ssd_decode_step(state_prev, x[:, -1], dt[:, -1], A,
                                       Bm[:, -1], Cm[:, -1])
    np.testing.assert_allclose(np.asarray(y_t), np.asarray(y_all[:, -1]),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(state_t), np.asarray(state),
                               atol=1e-4, rtol=1e-3)


# --------------------- batched natural-spline fit ---------------------- #
def _spline_knots(n):
    return np.sort(RNG.choice(np.arange(1.0, 33.0), size=n, replace=False))


@pytest.mark.parametrize("n", [1, 2, 3, 5, 9, 16])
def test_nat_spline_fit_ref_matches_numpy(n):
    """Acceptance: the vmapped Thomas solve matches the numpy offline-refit
    path (``spline.nat_spline_coeffs``) to <= 1e-5."""
    from repro.core.spline import nat_spline_coeffs

    x = _spline_knots(n)
    Y = RNG.normal(size=(37, n))
    want = nat_spline_coeffs(x, Y)
    got = np.asarray(ref.nat_spline_fit_ref(x, Y))
    assert got.shape == (37, max(n - 1, 1), 4)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n", [3, 5, 16])
def test_nat_spline_fit_pallas_matches_ref(n):
    from repro.kernels.spline_fit import nat_spline_fit_pallas

    x = _spline_knots(n)
    Y = RNG.normal(size=(37, n))  # 37 rows: exercises the padding path
    want = np.asarray(ref.nat_spline_fit_ref(x, Y))
    got = np.asarray(nat_spline_fit_pallas(x, Y, rb=16, interpret=True))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_nat_spline_fit_pallas_degenerate_knots_delegate():
    from repro.kernels.spline_fit import nat_spline_fit_pallas
    from repro.core.spline import nat_spline_coeffs

    for n in (1, 2):
        x = _spline_knots(n)
        Y = RNG.normal(size=(5, n))
        got = np.asarray(nat_spline_fit_pallas(x, Y, interpret=True))
        np.testing.assert_allclose(got, nat_spline_coeffs(x, Y),
                                   rtol=1e-5, atol=1e-5)


def test_nat_spline_fit_coeffs_interpolate_knots():
    """The fitted coefficients reproduce every data point exactly."""
    from repro.core.spline import nat_spline_eval
    from repro.kernels.ops import nat_spline_fit

    x = np.array([1.0, 3.0, 4.0, 9.0, 12.0, 16.0])
    Y = RNG.normal(size=(5, 6))
    coeffs = np.asarray(nat_spline_fit(x, Y), np.float64)
    got = nat_spline_eval(x, coeffs, x)
    np.testing.assert_allclose(got, Y, rtol=1e-4, atol=1e-4)


# ------------------ batched transfer-surface selection ------------------ #
SELECT_CASES = [
    # (S, G, B, P, bb): non-block-multiple B exercises the padding path
    (3, 64, 17, 4, 8),
    (5, 256, 32, 16, 8),
    (2, 128, 7, 5, 4),
]


@pytest.mark.parametrize("case", SELECT_CASES)
def test_transfer_select_pallas_matches_ref(case):
    from repro.kernels.transfer_select import batched_predict_argmax_pallas

    S, G, B, P, bb = case
    values = RNG.normal(size=(S, G)).astype(np.float32) * 5.0
    idx = RNG.integers(0, G, size=(B, P)).astype(np.int32)
    best_r, argk_r = ref.batched_predict_argmax_ref(values, idx)
    best_p, argk_p = batched_predict_argmax_pallas(values, idx, bb=bb,
                                                   interpret=True)
    np.testing.assert_allclose(np.asarray(best_p), np.asarray(best_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(argk_p), np.asarray(argk_r))


def test_transfer_select_pallas_exact_at_block_8():
    """The kernel is a gather: values come back bit-exactly, and repeated
    candidates and tied values pick the first maximal column like argmax."""
    from repro.kernels.transfer_select import batched_predict_argmax_pallas

    S, G, B, P = 6, 512, 37, 16
    values = (RNG.integers(-8, 8, size=(S, G)) * 0.37).astype(np.float32)
    idx = RNG.integers(0, G, size=(B, P)).astype(np.int32)
    idx[:, 5] = idx[:, 2]  # a repeated candidate ties with an earlier one
    best_r, argk_r = ref.batched_predict_argmax_ref(values, idx)
    best_p, argk_p = batched_predict_argmax_pallas(values, idx, bb=8,
                                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(best_p), np.asarray(best_r))
    np.testing.assert_array_equal(np.asarray(argk_p), np.asarray(argk_r))


def test_transfer_select_ops_dispatch():
    from repro.kernels.ops import transfer_predict_argmax

    values = RNG.normal(size=(3, 64)).astype(np.float32)
    idx = RNG.integers(0, 64, size=(9, 4)).astype(np.int32)
    best_ref, argk_ref = transfer_predict_argmax(values, idx)
    best_pal, argk_pal = transfer_predict_argmax(values, idx, use_pallas=True,
                                                 interpret=True)
    np.testing.assert_allclose(np.asarray(best_pal), np.asarray(best_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(argk_pal), np.asarray(argk_ref))


# ------------------ batched nearest-centroid assignment ----------------- #
ASSIGN_CASES = [
    # (N, M, d): non-block-multiple N exercises the padding path
    (257, 3, 4),
    (1024, 8, 4),
    (1000, 12, 6),
]


@pytest.mark.parametrize("case", ASSIGN_CASES)
def test_cluster_assign_ref_matches_numpy(case):
    N, M, d = case
    X = RNG.normal(size=(N, d)) * 3.0
    C = RNG.normal(size=(M, d)) * 3.0
    lab, d2 = ref.cluster_assign_ref(X, C)
    want_d2 = ((X[:, None, :] - C[None]) ** 2).sum(-1)
    np.testing.assert_array_equal(np.asarray(lab), want_d2.argmin(1))
    np.testing.assert_allclose(np.asarray(d2), want_d2.min(1),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("case", ASSIGN_CASES)
def test_cluster_assign_pallas_matches_ref(case):
    from repro.kernels.cluster_assign import cluster_assign_pallas

    N, M, d = case
    X = RNG.normal(size=(N, d)) * 3.0
    C = RNG.normal(size=(M, d)) * 3.0
    lab_r, d2_r = ref.cluster_assign_ref(X, C)
    lab_p, d2_p = cluster_assign_pallas(X, C, nb=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(lab_p), np.asarray(lab_r))
    np.testing.assert_allclose(np.asarray(d2_p), np.asarray(d2_r),
                               rtol=1e-5, atol=1e-5)


def test_cluster_assign_ops_dispatch():
    from repro.kernels.ops import cluster_assign

    X = RNG.normal(size=(100, 4))
    C = RNG.normal(size=(5, 4))
    lab_ref, _ = cluster_assign(X, C)
    lab_pal, _ = cluster_assign(X, C, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(lab_ref), np.asarray(lab_pal))
