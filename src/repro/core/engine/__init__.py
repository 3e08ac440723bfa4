"""Unified fleet engines: one ``run_fleet`` facade over three schedulers.

``engine="threaded"`` is the original thread-per-session oracle
(``repro.core.fleet.FleetScheduler``); ``engine="vectorized"`` is the
event-loop engine that produces bit-identical ``FleetReport``s at parity
scale and runs 1e5+ concurrent sessions (``repro.core.engine.vectorized``);
``engine="sharded"`` runs the vectorized engine's loop at parity scale (the
strict regime, bit-identical) and bulk-synchronous windows above it, with
fleet slots spread over ``n_shards`` host-side event heaps
(``repro.core.engine.sharded``).  Both loops share one per-run bookkeeping
of admission, recovery and finish.  ``n_shards`` defaults to the host's
device count; no shard runs on a device.
"""

from repro.core.engine.api import (
    VALID_CONTENTION,
    VALID_ENGINES,
    EngineConfig,
    run_fleet,
)
from repro.core.engine.heap import VectorEventHeap
from repro.core.engine.shard import (
    ShardedEventFrontier,
    WindowedLinkState,
)
from repro.core.engine.sharded import (
    DEFAULT_SHARD_WINDOW_S,
    ShardedFleetEngine,
)
from repro.core.engine.vectorized import (
    AUTO_CONTENTION_CUTOVER,
    FleetStateArrays,
    VectorizedFleetEngine,
)

__all__ = [
    "AUTO_CONTENTION_CUTOVER",
    "DEFAULT_SHARD_WINDOW_S",
    "EngineConfig",
    "FleetStateArrays",
    "ShardedEventFrontier",
    "ShardedFleetEngine",
    "VALID_CONTENTION",
    "VALID_ENGINES",
    "VectorEventHeap",
    "VectorizedFleetEngine",
    "WindowedLinkState",
    "run_fleet",
]
