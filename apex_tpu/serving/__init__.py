"""apex_tpu.serving — continuous-batching TPU inference engine.

Multi-tenant serving over the model zoo's ``decode=True`` KV-cache
path.  One engine, :class:`PagedEngine`: a block-pool KV-cache sized
in TOKENS with per-slot block tables
(:mod:`~apex_tpu.serving.cache`), chunked prefill riding inside the
fused mixed prefill+decode step, token-budget admission and
block-exhaustion preemption — HBM footprint and per-step bytes scale
with live tokens, not ``max_slots × max_seq_len``.  On top:
refcounted **copy-on-write prefix sharing** (``share_prefixes=True``
— a hot system prompt's KV pages are trie-matched at admission and
mapped once per replica instead of once per tenant),
**speculative decoding** (``spec_tokens=K`` — host-side
prompt-lookup drafts verified K-at-a-time in one mixed-step
application, accepted-prefix + bonus token per step),
**quantized pages** (``kv_dtype``) and
**tensor-parallel replicas** (``tp=M`` / ``mesh=`` — ONE replica
spans M chips: weights ride the GSPMD TP layers, the pool shards on
``kv_heads`` via the shard_map path of
:func:`~apex_tpu.ops.paged_attention.paged_attention`, block
tables / trie / allocator stay replicated host logic — the first
path that serves a model too big for one chip).

Plus a bounded FIFO queue with admission/eviction at step boundaries
(:mod:`~apex_tpu.serving.scheduler`), a threaded submit/stream
front-end with TTFT / step-latency / pool-occupancy telemetry
(:mod:`~apex_tpu.serving.api`), and a multi-replica fleet front door
(:mod:`~apex_tpu.serving.fleet`): least-loaded health-gated routing
across N replica servers with circuit breakers, graceful drain,
replica-kill tenant migration, and queue-depth/TTFT-driven scale
hooks.  Greedy decode through the engine is token-identical to
``apex_tpu.models.generate`` — including across a migration; steady
state is retrace-free and *enforced* so by
``tracecheck.retrace_guard``.  See docs/serving.md and docs/fleet.md.
"""

from apex_tpu.serving.api import (
    InferenceServer,
    ReplicaDraining,
    RequestFailed,
    RequestHandle,
    ServerClosed,
)
from apex_tpu.serving.fleet import (
    AutoscaleConfig,
    CircuitBreaker,
    FleetHandle,
    FleetRouter,
)
from apex_tpu.serving.engine import (
    PagedEngine,
    StepOutput,
    prompt_lookup_draft,
    tp_mesh,
)
from apex_tpu.serving.cache import (
    BlockAllocator,
    BlockExhausted,
    PrefixTrie,
    chain_digests,
)
from apex_tpu.serving.scheduler import (
    QueueFull,
    Request,
    Scheduler,
    StepEvent,
)

__all__ = [
    "InferenceServer",
    "RequestHandle",
    "RequestFailed",
    "ServerClosed",
    "ReplicaDraining",
    "FleetRouter",
    "FleetHandle",
    "CircuitBreaker",
    "AutoscaleConfig",
    "PagedEngine",
    "StepOutput",
    "BlockAllocator",
    "BlockExhausted",
    "PrefixTrie",
    "chain_digests",
    "prompt_lookup_draft",
    "tp_mesh",
    "Scheduler",
    "Request",
    "StepEvent",
    "QueueFull",
]
