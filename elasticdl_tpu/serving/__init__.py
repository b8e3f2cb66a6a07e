"""Online serving subsystem: continuous-batching generation server.

The training half of the north star is elastic training (master-owned
task queue, workers pull work); this package is the first subsystem on
the inference half — it turns the offline decode library
(api/generation.py) into a standing server:

* admission.py   bounded request queue with backpressure + deadlines
* engine.py      continuous-batching decode scheduler over a fixed
                 pool of slots whose KV rows live in the block-paged
                 pool (one jit step, no recompiles on membership
                 change)
* kv_pool.py     block-paged KV storage: free-list allocator, per-slot
                 block tables, shared per-layer block arenas, and the
                 tiered host-spill cache (evicted prefix chains park
                 in bounded host RAM and revive by upload)
* server.py      gRPC front-end (Generate / GenerateStream /
                 ServerStatus) + the scheduler thread
* router.py      health-checked multi-replica routing tier: heartbeat
                 leases, least-loaded dispatch, per-replica circuit
                 breakers, bounded re-dispatch + hedging, shed-load
                 (entry: python -m elasticdl_tpu.serving.router_main)
* hot_reload.py  checkpoint-dir watcher that swaps params between
                 decode steps without dropping in-flight requests
* telemetry.py   serving gauges/counters (closed name sets) on the
                 common/tb_events.py path, each backed by a windowed
                 time-series ring feeding the Prometheus /metrics
                 exposition and the router's SLO burn-rate engine
                 (observability/metrics.py, observability/slo.py)

See docs/designs/serving.md for the slot lifecycle and failure modes.
"""

from elasticdl_tpu.serving.admission import (  # noqa: F401
    AdmissionError,
    RequestQueue,
    ServingRequest,
)
from elasticdl_tpu.serving.engine import (  # noqa: F401
    PagedContinuousBatchingEngine,
)
from elasticdl_tpu.serving.kv_pool import (  # noqa: F401
    BlockAllocator,
    OutOfBlocks,
    PagedKVPool,
)
from elasticdl_tpu.serving.router import (  # noqa: F401
    CircuitBreaker,
    Router,
    RouterConfig,
    RouterError,
    RouterServicer,
)
from elasticdl_tpu.serving.server import (  # noqa: F401
    GenerationServer,
    ServingConfig,
)
