"""Clustering of historical logs (Sec. 3.1, Eqs. 2-5), small-n and at scale.

Implements both algorithms the paper evaluates:
  * K-means++ seeding + Lloyd iterations (O(log m)-competitive seeding),
  * HAC with UPGMA linkage over centroid distance (Eq. 2),
with the Calinski-Harabasz index (Eq. 3) for model-order selection.

Two compute paths share the ``ClusterModel`` contract:
  * the original pure-numpy path (exact Lloyd / HAC), retained as the
    small-n oracle and the default below ``BATCHED_THRESHOLD`` rows;
  * a batched JAX path for million-entry logs: mini-batch k-means++
    (Sculley 2010) trained for *every* candidate model order in ``m_range``
    simultaneously — one ``lax.scan`` sweep over shared mini-batches with
    the centroid tensors stacked over an m axis — followed by a few exact
    full-batch Lloyd refinement steps and a final full-data label pass
    through the tiled nearest-centroid kernel (``kernels.ops.cluster_assign``;
    Pallas on TPU).  CH model-order selection then scores all candidate
    orders from per-cluster sufficient statistics of that single label pass.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

# n at/above which fit_clusters routes "kmeans++" to the batched JAX path.
BATCHED_THRESHOLD = 4096

# Full-data passes process points in fixed-size chunks so live temporaries
# stay bounded regardless of n (shared by the jitted sweeps and assign_many).
_CHUNK = 65536


def kmeans_pp_init(X: np.ndarray, m: int, rng: np.random.Generator) -> np.ndarray:
    """K-means++ seeding (Arthur & Vassilvitskii 2007)."""
    n = X.shape[0]
    centers = [X[rng.integers(n)]]
    for _ in range(1, m):
        d2 = np.min(((X[:, None, :] - np.asarray(centers)[None]) ** 2).sum(-1), axis=1)
        total = d2.sum()
        if not np.isfinite(total) or total <= 1e-12:
            # degenerate data (all points coincide): uniform seeding
            centers.append(X[rng.integers(n)])
            continue
        centers.append(X[rng.choice(n, p=d2 / total)])
    return np.asarray(centers)


def kmeans(X: np.ndarray, m: int, *, iters: int = 50, seed: int = 0,
           init: np.ndarray | None = None) -> tuple[np.ndarray, np.ndarray]:
    """K-means++ clustering -> (labels (n,), centroids (m, d)).

    ``init`` overrides the k-means++ seeding with explicit starting
    centroids — the batched path's fixed-point fidelity check polishes its
    result with these exact Lloyd iterations.
    """
    rng = np.random.default_rng(seed)
    C = kmeans_pp_init(X, m, rng) if init is None else np.array(init, np.float64)
    labels = np.zeros(X.shape[0], np.int64)
    for _ in range(iters):
        d2 = ((X[:, None, :] - C[None]) ** 2).sum(-1)
        new = d2.argmin(1)
        if np.array_equal(new, labels) and _ > 0:
            break
        labels = new
        for k in range(m):
            mask = labels == k
            if mask.any():
                C[k] = X[mask].mean(0)
    return labels, C


def hac_upgma(X: np.ndarray, m: int) -> np.ndarray:
    """Agglomerative clustering, UPGMA update, centroid distance (Eq. 2).

    Merges the closest cluster pair until ``m`` clusters remain; the proximity
    matrix row/column of the merged pair is refreshed with the new centroid.
    """
    n = X.shape[0]
    active = list(range(n))
    centroid = {i: X[i].copy() for i in range(n)}
    size = {i: 1 for i in range(n)}
    members: dict[int, list[int]] = {i: [i] for i in range(n)}
    # proximity matrix over active clusters
    D = np.full((n, n), np.inf)
    for i in range(n):
        d = np.sqrt(((X - X[i]) ** 2).sum(-1))
        D[i] = d
        D[i, i] = np.inf
    nxt = n
    while len(active) > m:
        sub = np.ix_(active, active)
        flat = D[sub]
        a_idx, b_idx = np.unravel_index(np.argmin(flat), flat.shape)
        a, b = active[a_idx], active[b_idx]
        # UPGMA: new centroid is the size-weighted mean of the merged pair.
        ca = (size[a] * centroid[a] + size[b] * centroid[b]) / (size[a] + size[b])
        centroid[nxt] = ca
        size[nxt] = size[a] + size[b]
        members[nxt] = members[a] + members[b]
        active.remove(a)
        active.remove(b)
        if nxt >= D.shape[0]:
            D = np.pad(D, ((0, n), (0, n)), constant_values=np.inf)
        for o in active:
            D[nxt, o] = D[o, nxt] = np.sqrt(((ca - centroid[o]) ** 2).sum())
        D[nxt, nxt] = np.inf
        active.append(nxt)
        nxt += 1
    labels = np.zeros(n, np.int64)
    for k, cid in enumerate(active):
        labels[members[cid]] = k
    return labels


def ch_index(X: np.ndarray, labels: np.ndarray) -> float:
    """Calinski-Harabasz index (Eq. 3): between/within variance ratio."""
    n = X.shape[0]
    ks = np.unique(labels)
    m = len(ks)
    if m < 2 or m >= n:
        return -np.inf
    overall = X.mean(0)
    between = 0.0
    within = 0.0
    for k in ks:
        pts = X[labels == k]
        c = pts.mean(0)
        between += len(pts) * ((c - overall) ** 2).sum()
        within += ((pts - c) ** 2).sum()
    if within <= 1e-12:
        return np.inf
    return float((between / (m - 1)) / (within / (n - m)))


def label_agreement(a: np.ndarray, b: np.ndarray) -> float:
    """Fraction of points two labelings agree on, up to cluster permutation.

    Solves the optimal one-to-one cluster matching over the confusion matrix
    (Hungarian algorithm), so relabelings of the same partition score 1.0.
    Used by the scale benchmark and the batched-vs-numpy parity tests.
    """
    from scipy.optimize import linear_sum_assignment
    a = np.asarray(a, np.int64).ravel()
    b = np.asarray(b, np.int64).ravel()
    if a.size != b.size or a.size == 0:
        raise ValueError("labelings must be the same non-zero length")
    conf = np.zeros((int(a.max()) + 1, int(b.max()) + 1))
    np.add.at(conf, (a, b), 1.0)
    ri, ci = linear_sum_assignment(-conf)
    return float(conf[ri, ci].sum() / a.size)


@dataclasses.dataclass
class ClusterModel:
    labels: np.ndarray
    centroids: np.ndarray
    m: int
    method: str
    ch: float
    # Per-centroid point counts (Sculley 2010 learning-rate state).  Fit
    # paths persist the final-labeling counts so streaming ``partial_fit``
    # updates continue the mini-batch schedule the offline fit would have
    # used; None on models built before this field existed (older pickles,
    # hand-built models) — ``_ensure_counts`` rebuilds from labels then.
    counts: np.ndarray | None = None

    def assign(self, x: np.ndarray) -> int:
        """Nearest-centroid assignment for a new feature vector."""
        return int(((self.centroids - x[None]) ** 2).sum(-1).argmin())

    def assign_many(self, X: np.ndarray, *,
                    use_pallas: bool = False) -> np.ndarray:
        """Nearest-centroid assignment for many feature vectors at once.

        The default path is chunked float64 numpy — arithmetic-identical to
        :meth:`assign`, so how an entry is routed can never depend on how
        large a batch it arrived in (the refresh subsystem's determinism
        guarantee).  ``use_pallas=True`` routes through the tiled Pallas
        assignment kernel instead (the TPU deployment path, float32).
        """
        if use_pallas:
            from repro.kernels import ops
            lab, _ = ops.cluster_assign(np.asarray(X, np.float32),
                                        np.asarray(self.centroids, np.float32),
                                        use_pallas=True)
            return np.asarray(lab, np.int64)
        X = np.atleast_2d(np.asarray(X, np.float64))
        out = np.empty(X.shape[0], np.int64)
        for i in range(0, X.shape[0], _CHUNK):
            blk = X[i:i + _CHUNK]
            d2 = ((self.centroids[None] - blk[:, None, :]) ** 2).sum(-1)
            out[i:i + _CHUNK] = d2.argmin(1)
        return out

    def _ensure_counts(self) -> np.ndarray:
        """Per-centroid counts, rebuilt from the fit labels when absent."""
        if self.counts is None:
            if self.labels is not None and self.labels.size:
                self.counts = np.bincount(
                    np.asarray(self.labels, np.int64),
                    minlength=self.m).astype(np.float64)
            else:
                self.counts = np.ones(self.m, np.float64)
        return self.counts

    def partial_fit(self, X: np.ndarray, *,
                    use_pallas: bool = False) -> np.ndarray:
        """Fold a mini-batch of new points into the centroids in place.

        One Sculley (2010) mini-batch k-means step, the numpy twin of the
        jitted ``minibatch_sweep`` arithmetic: assign the batch to the
        current centroids, then move each winning centroid toward its batch
        mean with the cumulative 1/counts learning rate.  Assignment goes
        through :meth:`assign_many`, so routing is arithmetic-identical to
        the scalar query path regardless of batch size.  Returns the batch
        labels so callers can reuse them (e.g. ``OfflineDB.update``'s
        ``assignments=``) without a second assignment pass.
        """
        X = np.atleast_2d(np.asarray(X, np.float64))
        labels = self.assign_many(X, use_pallas=use_pallas)
        counts = self._ensure_counts()
        cnt = np.bincount(labels, minlength=self.m).astype(np.float64)
        sums = np.zeros_like(self.centroids, np.float64)
        np.add.at(sums, labels, X)
        counts += cnt
        lr = np.where(cnt > 0, cnt / np.maximum(counts, 1.0), 0.0)
        tgt = sums / np.maximum(cnt, 1.0)[:, None]
        self.centroids += lr[:, None] * (tgt - self.centroids)
        return labels


# --------------------------------------------------------------------- #
# batched path: mini-batch k-means++ over the whole m_range in one sweep
# --------------------------------------------------------------------- #
# Unused (padded) centroid slots carry this coordinate value: their squared
# distance to any real point is ~1e12, so they can never win an argmin, and
# winning nothing means they are never updated — no masking tensors needed.
_SENTINEL = 1.0e6


@functools.lru_cache(maxsize=1)
def _jax_sweeps():
    """Lazily-built jitted sweeps (keeps numpy-only callers jax-free)."""
    import jax
    import jax.numpy as jnp

    # Every product here runs at HIGHEST precision: at the default, the
    # TPU's MXU rounds f32 operands to bf16, which moves the distances and
    # the cluster sums enough to leave the fit off float64 Lloyd's fixed
    # point (0.9936 label agreement at 1e6 rows on a TPU v5e, against
    # 0.9998 on the CPU).
    hi = jax.lax.Precision.HIGHEST

    def _assign(xc, Cf, K, M):
        """(CH, d) points vs (K*M, d) stacked centroids -> (CH, K) labels.

        The flattened twin of ``kernels.ref.cluster_assign_ref``: one
        (CH, d) x (d, K*M) matmul scores every model order's centroids at
        once; sentinel slots lose every argmin, so labels stay in [0, m).
        """
        x2 = (xc * xc).sum(-1)[:, None]
        c2 = (Cf * Cf).sum(-1)[None, :]
        xcf = jnp.dot(xc, Cf.T, precision=hi)
        d2 = (x2 - 2.0 * xcf + c2).reshape(-1, K, M)
        return jnp.argmin(d2, axis=-1)

    @jax.jit
    def minibatch_sweep(X, C0, batches):
        """Mini-batch k-means for all model orders at once.

        X: (n, d); C0: (K, M, d) seeded centroids, sentinel-padded past
        each order's m; batches: (T, B) point indices shared by every
        order.  One scan step assigns a mini-batch under every order
        simultaneously and moves each winning centroid toward its batch
        mean with the 1/counts learning rate (Sculley 2010).  Centroids
        that win no points keep their previous position.
        """
        K, M, d = C0.shape

        def step(carry, idx):
            C, counts = carry
            xb = X[idx]                                       # (B, d)
            lab = _assign(xb, C.reshape(K * M, d), K, M)      # (B, K)
            oh = (lab[..., None] == jnp.arange(M)[None, None, :]
                  ).astype(jnp.float32)                       # (B, K, M)
            cnt = oh.sum(0)                                   # (K, M)
            sums = jnp.einsum("bkm,bd->kmd", oh, xb, precision=hi)
            counts = counts + cnt
            lr = jnp.where(cnt > 0, cnt / jnp.maximum(counts, 1.0), 0.0)
            tgt = sums / jnp.maximum(cnt, 1.0)[..., None]
            C = C + lr[..., None] * (tgt - C)
            return (C, counts), None

        counts0 = jnp.zeros((K, M), jnp.float32)
        (C, _), _ = jax.lax.scan(step, (C0, counts0), batches)
        return C

    @jax.jit
    def refine_and_stats(Xc, wc, C0, steps):
        """Exact Lloyd refinement + final labels/statistics, all orders.

        Xc: (nc, CH, d) chunked zero-padded points; wc: (nc, CH) 1.0 for
        real rows; ``steps`` is a dummy (R,) axis giving the refinement
        step count.  Returns the refined centroids, the final full-data
        labels (n_pad, K), and the per-(order, cluster) point counts and
        coordinate sums of that final labeling — the sufficient statistics
        the CH model-order selection needs, so scoring every candidate m
        costs no extra pass over the data.  Empty clusters keep stale
        centroids.
        """
        K, M, d = C0.shape

        def data_pass(C, want_labels):
            Cf = C.reshape(K * M, d)

            def acc(carry, inp):
                sums, cnt = carry
                xc, wv = inp                                  # (CH, d), (CH,)
                lab = _assign(xc, Cf, K, M)                   # (CH, K)
                oh = (lab[..., None] == jnp.arange(M)[None, None, :]
                      ).astype(jnp.float32) * wv[:, None, None]
                sums = sums + jnp.einsum("bkm,bd->kmd", oh, xc,
                                         precision=hi)
                cnt = cnt + oh.sum(0)
                ys = lab.astype(jnp.int32) if want_labels else None
                return (sums, cnt), ys

            z = (jnp.zeros((K, M, d), jnp.float32),
                 jnp.zeros((K, M), jnp.float32))
            return jax.lax.scan(acc, z, (Xc, wc))

        def step(C, _):
            (sums, cnt), _ = data_pass(C, False)
            new = sums / jnp.maximum(cnt, 1.0)[..., None]
            return jnp.where(cnt[..., None] > 0, new, C), None

        C, _ = jax.lax.scan(step, C0, steps)
        (sums, cnt), labs = data_pass(C, True)                # labs (nc, CH, K)
        return C, sums, cnt, labs.reshape(-1, K)

    return minibatch_sweep, refine_and_stats


def _ch_from_labels(X: np.ndarray, labels: np.ndarray, m: int
                    ) -> tuple[float, np.ndarray, np.ndarray]:
    """CH index + exact centroids + counts from one label pass.

    Per-cluster counts / coordinate sums come from ``np.bincount`` (O(n d)),
    so scoring every candidate order costs one pass over the labels instead
    of a fresh O(n m d) distance computation.
    """
    n, d = X.shape
    cnt = np.bincount(labels, minlength=m).astype(np.float64)
    sums = np.stack([np.bincount(labels, weights=X[:, j], minlength=m)
                     for j in range(d)], axis=1)              # (m, d)
    score, cents = _ch_from_stats(n, float((X * X).sum()), X.mean(0),
                                  cnt, sums)
    return score, cents, cnt


def _ch_from_stats(n: int, sq_total: float, overall: np.ndarray,
                   cnt: np.ndarray, sums: np.ndarray) -> tuple[float, np.ndarray]:
    """CH index + exact centroids from per-cluster sufficient statistics.

    ``within = sum |x|^2 - sum_k n_k |c_k|^2`` when ``c_k`` is the exact
    assignment mean, so one (cnt, sums) pair scores a candidate order in
    O(m d) — no extra pass over the data.
    """
    cents = sums / np.maximum(cnt, 1.0)[:, None]
    occ = cnt > 0
    m_eff = int(occ.sum())
    if m_eff < 2 or m_eff >= n:
        return -np.inf, cents
    within = max(sq_total - float((cnt[occ] * (cents[occ] ** 2).sum(-1)).sum()),
                 0.0)
    between = float((cnt[occ] * ((cents[occ] - overall[None]) ** 2).sum(-1)
                     ).sum())
    if within <= 1e-12 * max(sq_total, 1.0):
        return np.inf, cents
    return float((between / (m_eff - 1)) / (within / (n - m_eff))), cents


def fit_clusters_batched(X: np.ndarray, *, m_range: range | None = None,
                         seed: int = 0, batch_size: int = 2048,
                         minibatch_iters: int = 80, refine_iters: int = 5,
                         init_subsample: int = 8192,
                         use_pallas: bool = False) -> ClusterModel:
    """Batched clustering with CH model-order selection, for large logs.

    Every candidate order in ``m_range`` is seeded with k-means++ on a
    shared subsample, trained together through one mini-batch scan sweep
    (the centroid tensors are stacked over an m axis), polished with a few
    exact full-batch Lloyd steps, and labeled in one final full-data pass
    that also emits every order's per-cluster sufficient statistics — the
    CH index then scores the whole ``m_range`` without touching the data
    again.  Largest CH wins, first such order on ties (the numpy path's
    selection rule).  ``use_pallas=True`` routes the final label pass
    through the tiled Pallas assignment kernel per order instead of the
    fused XLA sweep.
    """
    import jax.numpy as jnp
    X = np.ascontiguousarray(np.asarray(X, np.float64))
    n, d = X.shape
    if m_range is None:
        m_range = range(2, min(9, max(3, n // 8)))
    ms = [int(m) for m in m_range if 2 <= m < n]
    if n < 3 or not ms:
        raise ValueError(
            f"cannot cluster {n} points over m_range={list(m_range)!r}: "
            "need at least 3 points and one order with 2 <= m < n")
    rng = np.random.default_rng(seed)
    sub = (X if n <= init_subsample
           else X[rng.choice(n, init_subsample, replace=False)])
    K, M = len(ms), max(ms)
    C0 = np.full((K, M, d), _SENTINEL)
    for i, m in enumerate(ms):
        C0[i, :m] = kmeans_pp_init(sub, m, rng)
    B = min(batch_size, n)
    batches = rng.integers(0, n, size=(minibatch_iters, B))

    minibatch_sweep, refine_and_stats = _jax_sweeps()
    Xf = jnp.asarray(X, jnp.float32)
    C = minibatch_sweep(Xf, jnp.asarray(C0, jnp.float32),
                        jnp.asarray(batches, jnp.int32))
    pad = (-n) % _CHUNK if n >= _CHUNK else 0
    if pad:
        Xp = jnp.concatenate([Xf, jnp.zeros((pad, d), jnp.float32)])
        w = jnp.concatenate([jnp.ones(n, jnp.float32),
                             jnp.zeros(pad, jnp.float32)])
    else:
        Xp, w = Xf, jnp.ones(n, jnp.float32)
    nc = max((n + pad) // _CHUNK, 1)
    C, sums, cnt, labs = refine_and_stats(
        Xp.reshape(nc, -1, d), w.reshape(nc, -1), C,
        jnp.zeros(max(refine_iters, 0)))
    C = np.asarray(C, np.float64)
    sums = np.asarray(sums, np.float64)
    cnt = np.asarray(cnt, np.float64)

    sq_total = float((X * X).sum())
    overall = X.mean(0)
    best: ClusterModel | None = None
    best_i = -1
    for i, m in enumerate(ms):
        if use_pallas:
            from repro.kernels import ops
            lab, _ = ops.cluster_assign(Xf, jnp.asarray(C[i, :m], jnp.float32),
                                        use_pallas=True)
            lab = np.asarray(lab, np.int64)
            score, cents, cnt_m = _ch_from_labels(X, lab, m)
        else:
            lab = None  # materialized lazily for the winning order only
            score, cents = _ch_from_stats(n, sq_total, overall,
                                          cnt[i, :m], sums[i, :m])
            cnt_m = cnt[i, :m]
        # clusters that won no points keep their trained (stale) centroid
        cents = np.where((cnt_m > 0)[:, None], cents, C[i, :m])
        cand = ClusterModel(lab, cents, m, "kmeans++", score,
                            counts=np.asarray(cnt_m, np.float64).copy())
        if best is None or score > best.ch:
            best, best_i = cand, i
    assert best is not None  # ms non-empty, checked above
    if best.labels is None:
        best.labels = np.asarray(labs[:n, best_i], np.int64)
    return best


def fit_clusters(X: np.ndarray, *, m_range: range | None = None,
                 method: str = "kmeans++", seed: int = 0,
                 batched: bool | None = None, batch_size: int = 2048,
                 use_pallas: bool = False) -> ClusterModel:
    """Cluster with CH-index model-order selection (largest CH wins).

    ``method="kmeans++"`` routes to the batched JAX path when ``batched`` is
    True, or automatically at ``n >= BATCHED_THRESHOLD`` when ``batched`` is
    None; the pure-numpy exact path (the small-n oracle) handles the rest.
    ``method="hac"`` is always the numpy path — its O(n^2) proximity matrix
    is the reason the batched path exists.
    """
    n = X.shape[0]
    if method == "kmeans++":
        if batched is None:
            batched = n >= BATCHED_THRESHOLD
        if batched:
            return fit_clusters_batched(X, m_range=m_range, seed=seed,
                                        batch_size=batch_size,
                                        use_pallas=use_pallas)
    elif method != "hac":
        raise ValueError(f"unknown clustering method: {method}")
    if m_range is None:
        m_range = range(2, min(9, max(3, n // 8)))
    best: ClusterModel | None = None
    for m in m_range:
        if m >= n:
            break
        if method == "kmeans++":
            labels, _ = kmeans(X, m, seed=seed)
        else:
            labels = hac_upgma(X, m)
        score = ch_index(X, labels)
        cents = np.stack([X[labels == k].mean(0) if (labels == k).any()
                          else X.mean(0) for k in range(m)])
        cand = ClusterModel(labels, cents, m, method, score,
                            counts=np.bincount(
                                labels, minlength=m).astype(np.float64))
        if best is None or score > best.ch:
            best = cand
    if best is None:
        raise ValueError(
            f"cannot cluster {n} points over m_range={list(m_range)!r}: "
            "need at least 3 points and one order with 2 <= m < n")
    return best
