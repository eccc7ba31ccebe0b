"""Production serving runtime: paged KV cache + continuous batching.

Reference analogs: the serving stack around fused_multi_transformer
(PaddleNLP llm serving) and the TPU ragged-paged-attention line of work
(PAPERS.md: "Ragged Paged Attention: A High-Performance and Flexible LLM
Inference Kernel for TPU").

The static-batch decode path (models/generation.py) allocates one
[b, max_len] KV ring per generate() call: every sequence pays max_len of
HBM whether it uses it or not, finished sequences keep decoding as padding
until the whole batch drains, and a new request waits for the NEXT batch.
This package replaces that with the vLLM/TPU-serving shape:

  * blocks.py    — fixed-size token blocks carved from one preallocated
                   pool; per-sequence block tables; O(1) alloc/append/free
                   with immediate reuse; occupancy/fragmentation gauges in
                   the observability metrics registry.
  * paged.py     — the device-side paged KV pool ([num_blocks, block_size,
                   kv_heads, head_dim] per layer) + the PagedLayerCache
                   view the models' attention layers consume; prefill
                   scatter of a contiguous prefix into pages.
  * scheduler.py — continuous batching: admits queued requests into the
                   running decode batch every step, interleaves bounded
                   prefill chunks with decode steps, evicts finished
                   sequences (and frees their blocks) immediately.
  * engine.py    — ServingEngine: one compiled decode step over a fixed
                   set of slots (paged ragged attention, sampling inside
                   the program, page buffers donated), chunked prefill,
                   works unchanged with the int8 weight-only swap.
  * server.py    — stdlib HTTP front end (POST /generate) with
                   per-request telemetry: queue time, TTFT, tokens/s;
                   FleetServer exposes the same protocol over a
                   FleetRouter (plus /drain for rolling restarts).
  * fleet.py     — FleetRouter: prefix-cache-aware routing across N
                   replicas, heartbeat-lease failure detection + circuit
                   breakers, re-dispatch of in-flight requests off dead
                   replicas (bitwise-identical greedy output), hedged
                   retries past a TTFT deadline, graceful drain, and
                   fleet-level load shedding with jittered Retry-After.
  * fleet_proc.py — process-granularity replicas: each replica is a
                   supervised OS subprocess (own model + engine + HTTP
                   server) spoken to over the server.py wire protocol;
                   crash/hang/zombie survival via waitpid + heartbeat-
                   lease death detection, capped+jittered respawn, a
                   warm-up routing gate, and incarnation fence tokens.
  * speculative.py — draft-model-free self-speculation: n-gram prompt-
                   lookup drafting from each request's own history plus
                   the per-request adaptive-k throttle; the engine
                   verifies drafts in ONE multi-token dispatch and rolls
                   rejected positions back exactly.
  * observability.py — per-request lifecycle traces (chrome-trace
                   exportable), tier-labeled SLO histograms (TTFT, TPOT,
                   queue, e2e), goodput/shed counters, per-tick engine
                   gauges, serving anomaly detectors + the flight-
                   recorder arm that auto-dumps on regression.
  * fleet_observability.py — fleet-wide distributed tracing: router-
                   stamped trace context (attempt/cause) on every
                   placement, cross-replica merged chrome traces
                   (pid=replica, tid=slot), attempt-attributed SLO
                   histograms with fleet rollups, and fleet anomaly
                   detectors (hedge spike, re-dispatch storm, breaker
                   flap, replica TTFT skew) with router-state dumps.
"""
from .blocks import BlockAllocator, WindowRings  # noqa: F401
from .observability import (  # noqa: F401
    RequestTrace,
    ServingObservability,
    export_request_trace,
)
from .paged import PagedKVPool, PagedLayerCache  # noqa: F401
from .scheduler import Request, Scheduler  # noqa: F401
from .speculative import NgramDrafter, SpecState  # noqa: F401
from .engine import (  # noqa: F401
    EngineDrainingError,
    QueueFullError,
    ServingEngine,
)
from .fleet import (  # noqa: F401
    CircuitBreaker,
    FleetAutoscaler,
    FleetRequest,
    FleetRouter,
    Replica,
    build_fleet,
    parse_fleet_roles,
)
from .fleet_observability import (  # noqa: F401
    FleetObservability,
    export_fleet_trace,
)
from .fleet_proc import (  # noqa: F401
    ProcessReplica,
    ProcessReplicaSpec,
    build_process_fleet,
    wait_fleet_ready,
)
from .server import FleetServer, ServingServer  # noqa: F401

__all__ = [
    "BlockAllocator",
    "WindowRings",
    "CircuitBreaker",
    "EngineDrainingError",
    "FleetAutoscaler",
    "FleetObservability",
    "FleetRequest",
    "FleetRouter",
    "FleetServer",
    "NgramDrafter",
    "PagedKVPool",
    "PagedLayerCache",
    "ProcessReplica",
    "ProcessReplicaSpec",
    "QueueFullError",
    "Replica",
    "Request",
    "RequestTrace",
    "Scheduler",
    "ServingEngine",
    "ServingObservability",
    "ServingServer",
    "SpecState",
    "build_fleet",
    "build_process_fleet",
    "parse_fleet_roles",
    "export_fleet_trace",
    "wait_fleet_ready",
    "export_request_trace",
]
