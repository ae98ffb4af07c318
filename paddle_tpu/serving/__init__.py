"""Inference serving runtime: dynamic batching over the AOT/fast path.

The reference ships a dedicated deployment stack (the C++ predictor under
paddle/fluid/inference/api/, the inference transpiler); paddle_tpu's
equivalent is this package: ``io.save_inference_model`` (optionally
``aot=True``) produces the artifact, and :class:`InferenceEngine` turns
it into a server —

    from paddle_tpu import serving

    engine = serving.InferenceEngine("model_dir",
                                     batch_buckets=(2, 4, 8, 16),
                                     batch_timeout_ms=2.0)
    out = engine.predict({"x": x})            # sync, from any thread
    fut = engine.predict_async({"x": x})      # future with .result()
    engine.swap_model("model_dir_v2")         # hot swap: load, drain, flip
    engine.stop()

To serve from every chip instead of one, swap the constructor for
:class:`ReplicaPool` — same surface, N device-pinned replicas behind
ONE shared admission queue, least-loaded pull dispatch, per-replica
circuit breakers + supervised workers, ROLLING ``swap_model`` (drain +
flip one replica at a time, capacity never zero), and autoscale
activate/quiesce driven by the ``SLOMonitor``'s
``serving.autoscale.desired_replicas`` signal (replica_pool.py;
docs/serving.md "Replica pool")::

    pool = serving.ReplicaPool("model_dir", replicas=4)   # jax.devices()
    out = pool.predict({"x": x})              # bitwise == engine.predict
    pool.start_autoscaler(obs.SLOMonitor([...], engine=pool))

Autoregressive generation rides the same engine: construct it with
``decode_model=`` (see ``models.transformer.build_decode_model``) and
call ``generate()``/``generate_async()`` — continuous batching
(iteration-level scheduling, Orca OSDI'22) over a paged KV cache
(vLLM/PagedAttention SOSP'23), bitwise-equal to per-sequence serving
with zero decode-step recompiles after warmup (decode_scheduler.py,
kv_cache.py; docs/serving.md "Autoregressive decode").  Long prompts
prefill in fixed-budget CHUNKS interleaved with decode iterations
(``DecodeConfig(prefill_chunk_tokens=...)`` — no more head-of-line
blocking; deadlines shed between chunks), and repeated prompt prefixes
map refcounted cached KV pages instead of recomputing
(``prefix_cache=True``, content-hash index + LRU eviction) — both
bitwise-neutral to the generated tokens (docs/serving.md "Chunked
prefill & prefix caching").

Adaptive request batching is the big serving-throughput lever on
accelerators (Clipper NSDI'17, Orca OSDI'22), and on TPU/XLA it
additionally wants a fixed menu of compiled batch shapes — exactly what
the executor's bound-program cache and the AOT export already provide:
the engine warms a bucket ladder of batch sizes once, then every live
request replays a compiled executable.  Results are bitwise-identical
to serving each request alone (see ``engine.py`` on the bucket floor),
backpressure and per-request deadlines fail with typed errors
(``ServingQueueFull`` / ``ServingTimeout``), model (re)load rides the
resilience retry choke points, and the whole runtime emits ``serving.*``
telemetry onto the observability registry (docs/serving.md lists the
schema).

Overload and failure are first-class (docs/serving.md "Priority classes
and admission control" / "Self-healing dispatch"): requests carry a
priority class (``interactive``/``batch``/``best_effort`` lanes with
per-class capacity) and deadlines shed AT ADMISSION with
``ServingOverloaded`` once the measured service rate says they can't be
met; PREDICT dispatch faults are retried (transient), bisected
(poison), and circuit-breaker-counted (persistent, ``ServingDegraded``
fast-fail + half-open recovery); DECODE dispatch faults retry
transients in place (``DecodeConfig.decode_retries`` — the paged-pool
updates are functional, so a failed attempt left the buffers intact)
and fail typed past the budget or on a fatal fault; a dead worker
thread (either path) is restarted by the supervisor — an admitted
request ALWAYS reaches a terminal outcome.

Decode is DURABLE under a ``ReplicaPool`` (docs/fault_tolerance.md
"Decode durability"): ``ReplicaPool(..., decode_model=...)`` runs one
``DecodeScheduler`` per replica behind a shared queue
(least-loaded-by-free-slots claim dispatch), and every request's
``DecodeJournal`` (prompt + pinned sampling knobs + accepted tokens;
O(tokens) host memory) makes its state portable: a replica death
evicts its in-flight sequences and REPLAYS them on siblings —
re-prefilling ``prompt + accepted``, bitwise-identical continuation
via absolute-position PRNG folding — bounded by
``DecodeConfig.replay_budget``.  ``GenerateRequest.cancel()`` retires
an abandoned generation at the next iteration boundary
(``ServingCancelled``), and the opt-in ``DecodeConfig(kv_guard=True)``
isfinite sweep fails exactly the sequence that wrote a non-finite KV
page (``KVCorruption``, pages scrubbed) instead of letting it poison
shared prefix pages.
``testing.faults.flaky_execute``/``slow_execute``/``poison_request``/
``kill_worker``/``kill_replica_mid_decode``/``corrupt_kv_page`` inject
each failure deterministically; ``benchmarks/bench_load.py`` +
``tools/check_slo.py`` gate goodput-under-deadline per class against
open-loop overload, and ``tools/check_decode_resilience.py`` gates the
kill-mid-decode bitwise-replay contract.

Multi-turn chat gets CONVERSATIONAL SESSIONS (sessions.py;
docs/serving.md "Sessions, affinity & disaggregated prefill"):
``generate(..., session="user-42")`` parks the finished turn's KV
pages refcount-PINNED in the owning replica's cache
(:class:`SessionStore`, TTL + capacity LRU; ``end_session()`` or
expiry releases the pins), prefix-affinity admission routes the next
turn back to the replica holding them (session-sticky →
longest-prefix-match → least-loaded, with health always overriding
affinity), and ``ReplicaPool(roles=("prefill", "decode", ...))``
disaggregates the phases — prefill-role replicas hand finished
prompts to decode-role siblings as host-staged ``HandoffPacket``
transfers.  Warm turns are bitwise-identical to cold full-history
re-prefill (``tools/check_sessions.py`` gates it); a dead owner's
conversation resumes on a sibling from its journal.
"""
from __future__ import annotations

from .batcher import CompletionTracker, DynamicBatcher
from .decode_scheduler import (
    DecodeConfig,
    DecodeJournal,
    DecodeModel,
    DecodeScheduler,
    GenerateRequest,
    HandoffPacket,
)
from .engine import BatchExecutor, InferenceEngine
from .errors import (
    KVCorruption,
    ServingCancelled,
    ServingClosed,
    ServingDegraded,
    ServingError,
    ServingOverloaded,
    ServingQueueFull,
    ServingQuotaExceeded,
    ServingTimeout,
)
from .kv_cache import PagedKVCache, PageGroup, write_token_kv
from .model_store import LoadedModel, ModelStore
from .replica_pool import ReplicaPool
from .request_queue import PRIORITY_CLASSES, Request, RequestQueue
from .resilient import CircuitBreaker, ResilientDispatcher, WorkerSupervisor
from .router import ModelRouter, RoutedRequest, TenantQuota
from .sessions import SessionRecord, SessionStore, scoped_session

__all__ = [
    "InferenceEngine",
    "ReplicaPool",
    "ModelRouter",
    "TenantQuota",
    "RoutedRequest",
    "BatchExecutor",
    "DynamicBatcher",
    "CompletionTracker",
    "ModelStore",
    "LoadedModel",
    "Request",
    "RequestQueue",
    "PRIORITY_CLASSES",
    "CircuitBreaker",
    "ResilientDispatcher",
    "WorkerSupervisor",
    "DecodeScheduler",
    "DecodeModel",
    "DecodeConfig",
    "DecodeJournal",
    "GenerateRequest",
    "HandoffPacket",
    "SessionStore",
    "SessionRecord",
    "scoped_session",
    "PagedKVCache",
    "PageGroup",
    "write_token_kv",
    "ServingError",
    "ServingTimeout",
    "ServingQueueFull",
    "ServingOverloaded",
    "ServingQuotaExceeded",
    "ServingDegraded",
    "ServingClosed",
    "ServingCancelled",
    "KVCorruption",
]
