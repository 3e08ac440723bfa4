"""Operations and bytes the algorithms need, and the chip's peaks.

The counts are the work of the algorithm, computed from its shapes, and
are the same whichever implementation runs it (the fused XLA sweeps or
the ``cluster_assign`` kernel): a kernel that does more work than this
gains no roofline share for it.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add the row with its source")
    return table[device_kind]


def discovery_sweep_work(n: int, d: int, ms: list[int], batch: int,
                         minibatch_iters: int, refine_iters: int
                         ) -> tuple[float, float]:
    """``(flops, bytes)`` of one discovery's sweeps over every model order.

    Each assignment of a point under order ``m`` takes ``3 d`` flops per
    centroid (difference, square, accumulate) and ``d`` adds to fold the
    point into its winner's sum.  The mini-batch sweep assigns
    ``minibatch_iters * batch`` points; the exact refinement makes
    ``refine_iters`` full passes and a final labelling pass.  Bytes: each
    pass reads the ``n x d`` float32 points once; the mini-batch sweep reads
    its gathered rows; the final pass writes one int32 label per point and
    order.  Centroid traffic (``K x M x d`` floats per step) is included.
    """
    per_point = sum(3 * d * m + d for m in ms)
    passes = refine_iters + 1
    points = minibatch_iters * batch + passes * n
    flops = float(per_point) * points
    cents = 4.0 * len(ms) * max(ms) * d
    nbytes = (4.0 * d * (minibatch_iters * batch + passes * n)
              + 4.0 * n * len(ms)
              + 2 * cents * (minibatch_iters + passes))
    return flops, nbytes


def least_time_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The larger of flops over peak FLOP/s and bytes over peak bytes/s."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")


# the jitted programs whose work ``discovery_sweep_work`` counts, by name
DISCOVERY_SWEEPS = ("minibatch_sweep", "refine_and_stats", "cluster_assign")


def sweep_seconds(summary) -> float:
    """Device seconds of the discovery sweeps in a trace summary."""
    return sum(t for name, t in summary.program_s.items()
               if name.startswith(DISCOVERY_SWEEPS))
