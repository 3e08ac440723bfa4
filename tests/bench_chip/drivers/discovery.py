"""Test support of the ``discovery`` driver: its tiny size, the faults
planted under its timed path, and the trace recorded on the chip that its
traced runs read in place of the CPU's."""
from __future__ import annotations

RECORDED = "discovery.xplane.pb.gz"
FAULTS = ["state_unchanged", "half_batch", "answer_altered",
          "labels_shifted"]


def shrink(config: dict, traffic: dict) -> tuple[dict, dict]:
    """Two logs of 8,192 rows, m from 4 to 6."""
    return config, {**traffic, "rows": 8192, "logs": 2, "m_range": [4, 6]}


def plant(kind: str, monkeypatch, config: dict) -> None:
    """Break the discovery under the window as ``kind`` says."""
    import numpy as np

    from repro.core import clustering

    if kind == "state_unchanged":
        # every update step hands its centroids back unchanged: the sweep
        # returns its seeds and the refinement makes no step
        _, refine = clustering._jax_sweeps()
        monkeypatch.setattr(clustering, "_jax_sweeps", lambda: (
            lambda X, C0, b: C0,
            lambda Xc, wc, C0, steps: refine(Xc, wc, C0, steps[:0])))
        return
    if kind not in FAULTS:
        raise ValueError(f"unknown fault {kind!r}")
    orig = clustering.fit_clusters

    def broken(X, **kw):
        if kind == "half_batch":
            cm = orig(X[: len(X) // 2], **kw)
            cm.labels = cm.assign_many(X)
            return cm
        cm = orig(X, **kw)
        if kind == "labels_shifted":
            # the label output read one row off, as a wrong slice would
            cm.labels = np.roll(cm.labels, 1)
            return cm
        cm.centroids = cm.centroids.copy()
        cm.centroids[0, 2] += 1e-2 * max(abs(cm.centroids[0, 2]), 1.0)
        return cm

    monkeypatch.setattr(clustering, "fit_clusters", broken)
