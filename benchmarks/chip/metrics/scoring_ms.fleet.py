"""Device time per fleet of admission scoring (ms).

The programs are matched by name.  ``predict_demands`` scores each
cluster's candidates through ``SurfaceStack.best_candidates``: on the
default path a few eager operations (the flat lattice index, the gather,
the max and argmax over candidates, the median surface's slice), each its
own program named after the operation; jitted, the gather and the Pallas
kernel run as ``batched_predict_argmax_*`` or ``_predict_*``.  A program
of any other name is not scoring and is not counted.
"""

EAGER = frozenset({"reshape", "squeeze", "subtract", "multiply", "add",
                   "_take", "_moveaxis", "_reduce_max", "_argmax",
                   "dynamic_slice"})
JITTED = ("batched_predict_argmax", "transfer_predict_argmax",
          "_predict_many", "_predict_points", "best_candidates")


def read(ctx):
    s, units = ctx["summary"], ctx["result"]["units"]
    if s is None or not units:
        return None
    t = sum(v for name, v in s.program_s.items()
            if name in EAGER or name.startswith(JITTED))
    return 1e3 * t / units if t > 0 else None
