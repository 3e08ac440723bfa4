"""Device time per discovery of the clustering sweeps (ms).

The programs are matched by name (``roofline.DISCOVERY_SWEEPS``): the
mini-batch sweep, the refinement and statistics pass, and the
``cluster_assign`` label kernel wherever the program routes the label pass
to it.
"""
from benchmarks.chip import roofline


def read(ctx):
    s, units = ctx["summary"], ctx["result"]["units"]
    t = roofline.sweep_seconds(s) if s is not None else 0.0
    return 1e3 * t / units if units and t > 0 else None
