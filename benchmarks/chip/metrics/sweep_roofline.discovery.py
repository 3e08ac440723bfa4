"""Least time of one discovery's sweeps over their device time (%).

The least time is the larger of the algorithm's flops over the chip's peak
FLOP/s and its bytes over the peak bandwidth, counted from the shapes by
``roofline.discovery_sweep_work``; the sweeps are bound by bytes.
"""
from benchmarks.chip import roofline


def read(ctx):
    s, units = ctx["summary"], ctx["result"]["units"]
    t = roofline.sweep_seconds(s) / units if s is not None and units else 0.0
    if t <= 0:
        return None
    tr = ctx["traffic"]
    lo, hi = tr["m_range"]
    sw = tr["sweep"]
    flops, nbytes = roofline.discovery_sweep_work(
        tr["rows"], 4, list(range(lo, hi + 1)), sw["batch"],
        sw["minibatch_iters"], sw["refine_iters"])
    least, _ = roofline.least_time_s(flops, nbytes,
                                     roofline.peaks(ctx["device_kind"]))
    return 100.0 * least / t
