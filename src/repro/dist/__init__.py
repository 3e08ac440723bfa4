"""Distribution subsystem: sharding rules, tuned collectives, pipeline
parallelism.

  * ``sharding``     — logical-axis -> mesh-axis rules with graceful
                       degradation (non-divisible dims replicate, reported).
  * ``collectives``  — gradient flatten/bucket/quantize all-reduce, plus the
                       paper bridge: a netsim model of the ICI fabric that
                       lets ``TransferTuner`` optimize bucketing parameters.
  * ``pipeline_par`` — GPipe-style pipeline parallelism over a ``stage``
                       mesh axis via collective-permute.

Submodules are imported explicitly by callers (never here) so that entry
points like ``launch/dryrun.py`` can set XLA flags before jax initializes.
"""
