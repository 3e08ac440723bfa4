"""Plain check that every chunk of a fleet ran under its link's one external
load.

Imports nothing of the program.  The configuration's ``load`` block states
the load: the diurnal term (``base_load``, ``peak_load``, ``peak_hour``,
``peak_width_h``) plus an AR(1) walk (coefficient ``ar``, innovation sd
``jitter``) laid on a grid of ``walk_step_s`` simulated seconds, clipped to
``[0, cap]``.  The walk is 0 at grid index 0; its innovations are drawn in
order from ``numpy.random.default_rng([seed, seed_stream])``, where
``seed`` is the ``env_seed`` of the fleet's request 0.  The load at ``t``
is::

    clip(base_load + peak_load * exp(-(d / peak_width_h)^2 / 2)
         + walk[max(floor(t / walk_step_s), 0)], 0, cap)

with ``d`` the hours from ``t``'s time of day to ``peak_hour``, round the
clock.

* ``load_gap`` -- largest distance, over every chunk of every session, of
  the external load the chunk reported from the link's load at the chunk's
  start.  The same formula in another library may differ by an ulp, so the
  limit leaves room for rounding and for nothing else.
"""
from __future__ import annotations

import numpy as np

DAY_S = 86400.0


def walk(load: dict, seed: int, n: int, dtype=np.float64) -> np.ndarray:
    """The walk's first ``n`` grid values, computed in ``dtype``."""
    rng = np.random.default_rng([seed, load["seed_stream"]])
    steps = rng.normal(0.0, load["jitter"], size=max(n - 1, 0)).astype(dtype)
    ar = dtype(load["ar"])
    out = [dtype(0.0)]
    for e in steps:
        out.append(ar * out[-1] + e)
    return np.array(out[:max(n, 1)], dtype)


def link_load(load: dict, seed: int, t: np.ndarray,
              dtype=np.float64) -> np.ndarray:
    """The link's external load at each simulated time of ``t``, computed
    in ``dtype`` (float64 for the check; the controls take float32)."""
    t = np.asarray(t, np.float64)
    if t.size == 0:
        return t
    k = np.maximum(np.floor(t / load["walk_step_s"]), 0).astype(np.int64)
    w = walk(load, seed, int(k.max()) + 1, dtype)[k]
    t = t.astype(dtype)
    hour = np.mod(t, dtype(DAY_S)) / dtype(3600.0)
    d = np.abs(hour - dtype(load["peak_hour"]))
    d = np.minimum(d, dtype(24.0) - d)
    diurnal = dtype(load["peak_load"]) * np.exp(
        dtype(-0.5) * (d / dtype(load["peak_width_h"])) ** 2)
    return np.clip(dtype(load["base_load"]) + diurnal + w, dtype(0.0),
                   dtype(load["cap"]))


def load_gap(chunks: np.ndarray, load: dict, seed: int) -> float:
    """``chunks``: ``(n, 2)`` rows of a chunk's start and the external load
    it reported, over every session of one fleet."""
    chunks = np.asarray(chunks, np.float64).reshape(-1, 2)
    if len(chunks) == 0:
        return 0.0
    want = link_load(load, seed, chunks[:, 0])
    return float(np.max(np.abs(chunks[:, 1] - want)))
