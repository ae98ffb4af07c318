"""Multi-replica serving: a device-mesh replica pool over one shared queue.

The single-device :class:`~.engine.InferenceEngine` tops out at one
chip's dispatch rate.  This module is the Clipper (NSDI'17) layered
answer scaled across ``jax.devices()``: N *replicas* — each one model
copy with its params committed (``device_put``) to its own device and
its own warmed bucket ladder — all fed from ONE shared priority
:class:`~.request_queue.RequestQueue`, so the global serving policies
stay global:

* **admission** (typed backpressure, per-class capacity, deadline-aware
  shedding) happens once, at the shared queue — a pool of 8 replicas
  sheds with the same grammar as one engine, and the shed estimator
  knows the rotation width (``RequestQueue.set_parallelism``);
* **dispatch is pull-based least-loaded**: every replica runs its own
  :class:`~.batcher.DynamicBatcher` worker against the shared queue and
  claims a batch only when its previous dispatch finished, so work
  flows to whichever replica is free — no assignment table to go stale
  when a replica slows down.  A *gate* checked before every claim is
  how a replica leaves the rotation without losing its thread, model,
  or compiled buckets: breaker open (ejected), rolling-swap drain, or
  autoscale quiesce (parked warm);
* **health is per replica**: each replica owns a
  :class:`~.resilient.CircuitBreaker` (consecutive fatal dispatches
  eject exactly that replica; its half-open probe re-admits it) and a
  supervised worker (a killed replica thread is restarted in place by
  the shared :class:`~.resilient.WorkerSupervisor`, with the in-flight
  batch failed typed, never hung — surviving replicas keep absorbing
  the queue meanwhile);
* **rolling hot swap**: :meth:`ReplicaPool.swap_model` drains + flips
  ONE replica at a time under live traffic, so serving capacity never
  reaches zero (contrast the engine's swap, which drains the whole
  queue watermark first).  Requests in flight when the swap starts
  finish on the version that claimed them; every answer is a complete
  output of exactly one version;
* **autoscale**: :meth:`autoscale_tick` consumes
  ``serving.autoscale.desired_replicas`` (the PR-8 ``SLOMonitor``
  signal) and activates/quiesces replicas within
  ``[min_replicas, max_replicas]`` — scale-up immediate, scale-down
  only after ``scale_down_after_s`` of consistently lower desire
  (no-thrash hysteresis).  Quiesce = stop claiming, let in-flight
  finish, park warm; reactivation is one flag flip away.
  :meth:`start_autoscaler` loops it from an in-process ``SLOMonitor``,
  the latest published gauge, or — ``metrics_url=`` — a live
  Prometheus-text ``/metrics`` scrape, so sizing can follow a monitor
  running in a different process entirely.

Bitwise contract: rows are computed independently of batch neighbors,
padding, and position (the engine's bucket-ladder contract), and every
replica runs the same compiled program — so per-request results are
bitwise-identical to the single-replica engine, whichever replica
serves them.  ``tools/check_replica_pool.py`` gates this, the >=2.5x
4-replica scaling floor, the never-zero-ready rolling swap, and the
kill/eject/revive cycle on the forced-host-device CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``).

**Pool-routed decode** (ISSUE 17): pass ``decode_model=`` (a
:class:`~.decode_scheduler.DecodeModel`) and the pool serves
``generate()`` / ``generate_async()`` too — each replica runs its own
:class:`~.decode_scheduler.DecodeScheduler` (own ``PagedKVCache``, own
warmed chunk/decode programs, pools committed to its device) behind ONE
shared decode :class:`~.request_queue.RequestQueue`, claimed
least-loaded-by-free-slots: a replica pulls only when no decode-ready
sibling has more free seats (ties claim, so equal replicas race the
queue and FIFO wins — no livelock).  Generation is *durable*: every
request's :class:`~.decode_scheduler.DecodeJournal` makes its decode
state portable, so when a replica's decode worker dies the supervisor
restart wrapper harvests the in-flight sequences
(:meth:`~.decode_scheduler.DecodeScheduler.evict_inflight`, run while
the worker is provably dead) and re-admits them to siblings, which
re-prefill ``prompt + accepted-so-far`` (prefix-cache warm where pages
survive) and continue BITWISE-identically — the sampling seed is pinned
at pool admission (a monotonic counter when the caller passes none),
because replay re-enqueues the request and a queue-seq-derived seed
would change mid-generation.  Re-admissions count on
``serving.decode.replays`` against ``DecodeConfig.replay_budget``
(typed ``ServingDegraded`` past it); each replica's decode dispatches
feed a per-replica decode breaker
(``serving.replica.decode_breaker_<i>``) consulted by its claim gate.
Autoscale quiesce and rolling predict-model swaps exclude a replica
from NEW decode claims (its active sequences finish in place); the
decode model itself is fixed at construction.  A pool built with
``model_dir=None`` serves decode only.

Telemetry: pool-level gauges ``serving.replica.pool_size`` /
``.active`` / ``.ready``; per-replica ``serving.replica.state_<i>``
(0 parked / 1 serving / 2 draining / 3 ejected / 4 dead),
``.inflight_rows_<i>``, ``.breaker_<i>``, counters
``.dispatches_<i>`` / ``.rows_<i>``; scale events on
``serving.replica.scale_ups`` / ``.scale_downs`` with a
``replica_scale`` record; per-replica flips during a rolling swap on
``serving.replica.swapped`` with ``replica_swap`` records; and every
execute span/record a replica emits carries its ``replica`` index, so
a request's trace tree names the replica that served it.
"""
from __future__ import annotations

import itertools
import threading
import time

import numpy as np

from .. import core as _core
from .. import observability as _obs
from .batcher import CompletionTracker, DynamicBatcher
from .decode_scheduler import DecodeConfig, DecodeScheduler, GenerateRequest
from .engine import BatchExecutor, normalize_feed
from .errors import ServingClosed, ServingDegraded, ServingError
from .model_store import ModelStore
from .request_queue import PRIORITY_CLASSES, Request, RequestQueue
from .resilient import CircuitBreaker, ResilientDispatcher, WorkerSupervisor

__all__ = ["ReplicaPool"]

_requests = _obs.counter("serving.requests")
_swaps = _obs.counter("serving.swaps")
_pool_size_gauge = _obs.gauge("serving.replica.pool_size")
_active_gauge = _obs.gauge("serving.replica.active")
_ready_gauge = _obs.gauge("serving.replica.ready")
_scale_ups = _obs.counter("serving.replica.scale_ups")
_scale_downs = _obs.counter("serving.replica.scale_downs")
_replica_swapped = _obs.counter("serving.replica.swapped")
# decode-path counters shared (by name) with decode_scheduler.py: pool
# admission and replay tick the same registry entries the schedulers do
_decode_requests = _obs.counter("serving.decode.requests")
_decode_replays = _obs.counter("serving.decode.replays")
# prefix-affinity dispatch (sessions.py): how each admission was routed
# (sticky to its session's replica / longest-prefix-match / no hint)
# and how often a stamped hint had to be stripped at the gate because
# the preferred replica could not take the work in time
_affinity_sticky = _obs.counter("serving.affinity.sticky")
_affinity_prefix = _obs.counter("serving.affinity.prefix")
_affinity_none = _obs.counter("serving.affinity.none")
_affinity_fallbacks = _obs.counter("serving.affinity.fallbacks")

#: serving.replica.state_<i> gauge codes
REPLICA_STATES = {"parked": 0, "serving": 1, "draining": 2, "ejected": 3,
                  "dead": 4}

# unique consumer-group keys for pools sharing one RequestQueue: two
# pools of the SAME deployment must still keep distinct rate EMAs
_POOL_IDS = itertools.count()


class _DevicePlace(_core.Place):
    """A Place pinned to one concrete jax device — the pool hands each
    replica its own entry from ``jax.devices()`` so the Program-backend
    executor compiles and dispatches there."""

    def __init__(self, device):
        super().__init__(int(getattr(device, "id", 0)))
        self._device = device

    def jax_device(self):
        return self._device

    def __repr__(self):
        return "_DevicePlace(%r)" % (self._device,)


class _Replica:
    """One model copy pinned to one device, with its own worker, breaker,
    dispatch pipeline, and accounting.  All mutable scheduling state
    (``active``/``draining``/``failed``) is flag-granular: the worker
    reads it at the gate, the pool writes it — no lock on the hot path."""

    def __init__(self, pool, index, device):
        self.index = index
        self.device = device
        self.store = ModelStore(place=_DevicePlace(device),
                                feed_shapes=pool._feed_shapes)
        self.model = None
        self.model_lock = threading.Lock()
        self.active = True          # in the rotation (autoscale flag)
        self.draining = False       # rolling-swap pause
        self.failed = False         # worker dead past its restart budget
        self.force_serve = False    # pool stop-drain: bypass the breaker
        self.decoder = None         # DecodeScheduler (decode_model= pools)
        self.decode_breaker = None  # its per-replica CircuitBreaker
        self.decode_failed = False  # decode worker dead past budget
        self.role = "both"          # decode role (ReplicaPool roles=)
        self.inflight_rows = 0      # rows the worker is dispatching NOW
        self.dispatches = 0
        self.rows_served = 0
        # last instant the worker was observed PARKED at the gate — the
        # drain handshake: the worker is single-threaded, so a park
        # stamped after drain began proves no dispatch is in flight
        self.parked_ts = 0.0
        self.breaker = CircuitBreaker(
            threshold=pool._breaker_threshold,
            cooldown_s=pool._breaker_cooldown_s,
            state_gauge=_obs.gauge("serving.replica.breaker_%d" % index))
        self._core = BatchExecutor(
            self._current_model, pool.batch_buckets,
            queue_depth=pool._queue.depth, tags={"replica": index})
        self.dispatcher = ResilientDispatcher(
            self._execute, max_retries=pool._execute_retries,
            breaker=self.breaker)
        self.batcher = DynamicBatcher(
            pool._queue, self.dispatcher, pool.max_batch_size,
            pool.batch_timeout_ms / 1e3,
            name="paddle-tpu-serving-replica%d" % index,
            tracker=pool._tracker, gate=self._gate,
            label="replica%d" % index,
            service_key=pool._consumer_key,
            owns_queue=pool._owns_queue)
        self._inflight_gauge = _obs.gauge(
            "serving.replica.inflight_rows_%d" % index)
        self._state_gauge = _obs.gauge("serving.replica.state_%d" % index)
        self._dispatch_counter = _obs.counter(
            "serving.replica.dispatches_%d" % index)
        self._rows_counter = _obs.counter("serving.replica.rows_%d" % index)

    # -- model ---------------------------------------------------------------
    def _current_model(self):
        with self.model_lock:
            return self.model

    def load_model(self, dirname, backend):
        """Load one model version PINNED to this replica's device:
        Program backend dispatch is pinned via the executor's place, and
        the loaded params are committed (``jax.device_put``) up front so
        only the per-request feed ever moves at dispatch time; the AOT
        backend's jitted executable is wrapped in a
        ``jax.default_device`` scope instead (its weights are baked into
        the executable, which compiles onto the device on first — i.e.
        warmup — call)."""
        import jax

        model = self.store.load(dirname, backend=backend)
        dev = self.device
        if model.kind == "aot":
            orig = model.predict_batch

            def pinned(feed, _orig=orig, _dev=dev):
                with jax.default_device(_dev):
                    return _orig(feed)

            model.predict_batch = pinned
        else:
            scope = getattr(model, "_scope", None)
            if scope is not None:
                # committed device_put BEFORE any dispatch (no fast-path
                # binding exists yet, so mutating values is safe): params
                # live on this replica's device from the first warmup run
                for name, val in list(scope.vars.items()):
                    try:
                        scope.vars[name] = jax.device_put(val, dev)
                    except (TypeError, ValueError):
                        pass   # non-array aux var: the executor feeds it
        return model

    # -- worker hot path -----------------------------------------------------
    def _gate(self):
        """Checked by the worker before every queue claim; False parks it
        (request stays in the shared queue for the other replicas)."""
        if self.force_serve and self.model is not None and not self.failed:
            # pool stop-drain: every queued request must reach a terminal
            # outcome NOW — an open breaker still dispatches (the
            # dispatcher fails requests typed if the path is truly dead,
            # which beats leaving them hanging at a closed gate)
            return True
        if (not self.active or self.draining or self.failed
                or self.model is None or not self.breaker.allow()):
            self.parked_ts = time.perf_counter()
            return False
        return True

    def _execute(self, requests):
        """One dispatch ATTEMPT (retries/bisected sub-batches re-enter
        here) with in-flight accounting around the shared pipeline."""
        rows = sum(r.rows for r in requests)
        self.inflight_rows += rows
        self._inflight_gauge.set(self.inflight_rows)
        try:
            self._core(requests)
            self.rows_served += rows
            self._rows_counter.inc(rows)
        finally:
            # runs for Exception AND BaseException (kill_worker): the
            # accounting is correct even as the worker thread dies
            self.inflight_rows -= rows
            self._inflight_gauge.set(self.inflight_rows)
            self.dispatches += 1
            self._dispatch_counter.inc()

    # -- health --------------------------------------------------------------
    def wait_quiescent(self, since, timeout):
        """Block until this replica provably has no dispatch in flight:
        its worker was seen parked at the (now closed) gate after
        ``since``, or the worker thread is dead with nothing in flight.
        False on timeout."""
        deadline = time.perf_counter() + timeout
        while True:
            if self.parked_ts > since:
                return True
            if not self.batcher.alive and self.inflight_rows == 0:
                return True
            if time.perf_counter() >= deadline:
                return False
            time.sleep(0.002)

    def state(self):
        if not self.batcher.alive or self.failed:
            return "dead"
        if self.breaker.state == "open":
            return "ejected"
        if self.draining:
            return "draining"
        if not self.active:
            return "parked"
        return "serving"

    def ready(self):
        """In rotation and able to claim work right now."""
        return (self.active and not self.draining and not self.failed
                and self.model is not None and self.batcher.alive
                and self.breaker.state != "open")

    def admissible(self):
        """Could serve an admitted request soon: not permanently failed
        and not breaker-open.  A draining/parked replica counts (the
        drain ends, the autoscaler re-activates) and so does a dead
        worker inside its restart budget (the supervisor revives it)."""
        return (not self.failed and self.model is not None
                and self.breaker.state != "open")

    def publish(self):
        self._state_gauge.set(REPLICA_STATES[self.state()])

    def stats(self):
        st = {
            "index": self.index,
            "device": str(self.device),
            "state": self.state(),
            "ready": self.ready(),
            "model_version": None if self.model is None
            else self.model.version,
            "worker_alive": self.batcher.alive,
            "breaker": self.breaker.state,
            "inflight_rows": self.inflight_rows,
            "dispatches": self.dispatches,
            "rows_served": self.rows_served,
            "batches": self.batcher.batches,
        }
        if self.decoder is not None:
            d = self.decoder.stats()
            d.update(alive=self.decoder.alive, failed=self.decode_failed,
                     breaker=self.decode_breaker.state,
                     free_slots=self.decoder.free_slots())
            st["decode"] = d
        return st


class ReplicaPool:
    """Serve one saved inference model from N device-pinned replicas.

    The external surface mirrors :class:`~.engine.InferenceEngine`
    (``predict`` / ``predict_async`` / ``swap_model`` / ``health`` /
    ``ready`` / ``stop`` / ``serve_metrics``), so anything written
    against the engine — the SLO monitor, the load harness, a client —
    scales to a pool by swapping the constructor.

    Parameters (beyond the engine's, which keep their meaning)
    ----------
    replicas: pool size (model copies / devices).  Default: one per
        entry of ``jax.devices()``.  Replica ``i`` is pinned to
        ``devices[i % len(devices)]``.
    devices: explicit device list (default ``jax.devices()``).
    min_replicas / max_replicas: autoscale clamp on the ACTIVE rotation
        (pool size itself is fixed at construction; a quiesced replica
        parks warm).  Defaults: 1 / ``replicas``.
    initial_replicas: rotation size at start (default: all).
    scale_down_after_s: hysteresis — desired must stay below the active
        count this long before a scale-down is applied (scale-UP is
        immediate; overload hurts now, idle capacity only costs money).
    decode_model / decode_config: enable pool-routed generation — one
        :class:`~.decode_scheduler.DecodeScheduler` per replica behind a
        shared decode queue with least-loaded claim dispatch, durable
        replay-on-death, and per-replica decode breakers (see the
        module docstring).  ``model_dir=None`` builds a decode-only
        pool (``predict`` then rejects typed).
    queue / tracker: share ONE admission ``RequestQueue`` and
        ``CompletionTracker`` with other pools (the router's cross-pool
        refactor): the pool registers itself as a consumer group for
        the shed estimator and never closes/drains a queue it does not
        own — the sharing coordinator does, after stopping every pool.
    model_label: deployment label stamped on every admitted request —
        keys the tenant/model-labeled per-class telemetry and this
        pool's consumer-group rate EMA.
    """

    def __init__(self, model_dir, replicas=None, devices=None,
                 min_replicas=1, max_replicas=None, initial_replicas=None,
                 batch_buckets=(2, 4, 8, 16), max_batch_size=None,
                 batch_timeout_ms=0.0, queue_capacity=256,
                 class_capacity=None, default_deadline_ms=None,
                 backend="auto", feed_shapes=None, warmup=True,
                 autostart=True, execute_retries=2, breaker_threshold=5,
                 breaker_cooldown_s=1.0, supervise=True,
                 worker_max_restarts=3, supervisor_interval_s=0.1,
                 scale_down_after_s=5.0, decode_model=None,
                 decode_config=None, queue=None, tracker=None,
                 model_label=None, sessions=None, roles=None,
                 affinity_timeout_s=1.0):
        import jax

        buckets = sorted(set(int(b) for b in batch_buckets))
        if not buckets or buckets[0] < 1:
            raise ValueError("batch_buckets must be positive ints, got %r"
                             % (batch_buckets,))
        self.batch_buckets = tuple(buckets)
        self.max_batch_size = int(max_batch_size or buckets[-1])
        self.batch_timeout_ms = float(batch_timeout_ms)
        self.default_deadline_ms = default_deadline_ms
        self._warmup = bool(warmup)
        self._feed_shapes = feed_shapes
        self._execute_retries = int(execute_retries)
        self._breaker_threshold = breaker_threshold
        self._breaker_cooldown_s = float(breaker_cooldown_s)
        devices = list(devices if devices is not None else jax.devices())
        if not devices:
            raise ServingError("no devices available for a replica pool")
        n = int(replicas) if replicas is not None else len(devices)
        if n < 1:
            raise ValueError("replicas must be >= 1")
        self.min_replicas = max(1, int(min_replicas))
        self.max_replicas = min(n, int(max_replicas)) if max_replicas \
            else n
        if self.min_replicas > self.max_replicas:
            raise ValueError("min_replicas %d > max_replicas %d"
                             % (self.min_replicas, self.max_replicas))
        self.scale_down_after_s = float(scale_down_after_s)
        self._state = "loading"
        # queue=/tracker=: share ONE admission queue + completion
        # watermark across pools (the DecodeScheduler's pool-mode
        # pattern lifted a level): the pool never closes or drains a
        # queue it does not own — the sharing coordinator (the router,
        # or the test harness) does, once every sharing pool stopped.
        self.model_label = model_label
        self._owns_queue = queue is None
        self._queue = queue if queue is not None else RequestQueue(
            queue_capacity, class_capacity=class_capacity)
        self._tracker = tracker if tracker is not None \
            else CompletionTracker()
        self._consumer_key = "%s#%d" % (model_label or "pool",
                                        next(_POOL_IDS))
        self._swap_lock = threading.Lock()
        self._scale_lock = threading.Lock()
        self._below_since = None      # scale-down hysteresis window start
        self._below_peak = 0          # max desired seen inside the window
        self._telemetry = _obs.get_telemetry()
        self._metrics_server = None
        self._replicas = [_Replica(self, i, devices[i % len(devices)])
                          for i in range(n)]
        if model_dir is None and decode_model is None:
            raise ServingError(
                "pass model_dir= (predict), decode_model= (generate), "
                "or both — an empty pool serves nothing")
        if model_dir is not None:
            for rep in self._replicas:
                rep.model = rep.load_model(model_dir, backend)
                if self._warmup:
                    rep.model.warmup(self.batch_buckets)
        active0 = self.max_replicas if initial_replicas is None else max(
            self.min_replicas, min(int(initial_replicas),
                                   self.max_replicas))
        for rep in self._replicas:
            rep.active = rep.index < active0
        # LIVE consumer count for the deadline-shed estimator: breaker
        # ejects, autoscale parks, worker deaths/revivals all reflect at
        # the next admission estimate with no bookkeeping at each flip.
        # Registered as a consumer GROUP (keyed by this pool) so a
        # shared queue sums each pool's count x its own rate EMA; the
        # legacy parallelism callable stays as the all-groups-cold
        # fallback — and the sole estimator for a pool-owned queue
        # before the first keyed sample lands.
        self._queue.register_consumers(self._consumer_key,
                                       lambda: len(self._ready()))
        if self._owns_queue:
            self._queue.set_parallelism(lambda: max(1, len(self._ready())))
        self._decode_enabled = decode_model is not None
        self._decode_config = None
        self._decode_queue = None
        self._sessions = None
        self._affinity_timeout_s = float(affinity_timeout_s)
        self._session_sweep_ts = time.perf_counter()
        self._roles = None
        if roles is not None and decode_model is None:
            raise ServingError(
                "roles= specializes DECODE replicas; pass decode_model=")
        if self._decode_enabled:
            dcfg = self._decode_config = decode_config or DecodeConfig()
            if roles is not None:
                role_list = [str(r) for r in roles]
                if len(role_list) != n:
                    raise ServingError(
                        "roles needs one entry per replica (%d), got %d"
                        % (n, len(role_list)))
                bad = [r for r in role_list
                       if r not in ("both", "prefill", "decode")]
                if bad:
                    raise ServingError(
                        "roles must be 'both'/'prefill'/'decode', got %s"
                        % bad)
                if not any(r in ("both", "prefill") for r in role_list):
                    raise ServingError(
                        "roles leave no prefill-capable replica")
                if not any(r in ("both", "decode") for r in role_list):
                    raise ServingError(
                        "roles leave no decode-capable replica")
                self._roles = tuple(role_list)
            # conversational sessions: sessions=False disables; a
            # SessionStore instance is used as-is (shareable for tests);
            # None auto-enables one whenever the prefix cache is on —
            # a pin is an extra refcount on the prefix index's chain,
            # so there is nothing to park without it
            if sessions is None:
                if dcfg.prefix_cache:
                    from .sessions import SessionStore
                    self._sessions = SessionStore()
            elif sessions is not False:
                if not dcfg.prefix_cache:
                    raise ServingError(
                        "sessions require DecodeConfig(prefix_cache=True)")
                self._sessions = sessions
            # admission-order seed pinning: replay re-enqueues a request
            # (reassigning its queue seq), so a seedless sampling request
            # gets a POOL-pinned seed here — stable across replays, and
            # identical between a fault-free and a faulted run admitting
            # the same requests in the same order
            self._decode_seed_lock = threading.Lock()
            self._decode_admissions = 0
            self._decode_queue = RequestQueue(
                dcfg.queue_capacity,
                depth_gauge=_obs.gauge("serving.decode.queue_depth"),
                full_counter=_obs.counter("serving.decode.queue_full"),
                shed_counter=_obs.counter("serving.decode.shed_admission"),
                gauge_prefix="serving.decode.queue_depth")
            self._decode_queue.set_parallelism(
                lambda: max(1, sum(1 for r in self._replicas
                                   if self._decode_claimable(r))))
            for rep in self._replicas:
                rep.role = (self._roles[rep.index]
                            if self._roles is not None else "both")
                rep.decode_breaker = CircuitBreaker(
                    threshold=self._breaker_threshold,
                    cooldown_s=self._breaker_cooldown_s,
                    state_gauge=_obs.gauge(
                        "serving.replica.decode_breaker_%d" % rep.index))
                # build + warm INSIDE the device scope so the compiled
                # steps and warmup dispatches land on this replica's
                # device; ``device=`` COMMITS the cache and the replica's
                # copy of the weights there — the worker thread dispatches
                # outside any scope, and committed arguments are what keep
                # the step on this device
                with jax.default_device(rep.device):
                    rep.decoder = DecodeScheduler(
                        decode_model, config=dcfg, autostart=False,
                        queue=self._decode_queue,
                        gate=(lambda r=rep: self._decode_gate(r)),
                        name="decode-replica%d" % rep.index,
                        evict_on_death=True, breaker=rep.decode_breaker,
                        sessions=self._sessions,
                        replica_index=rep.index, role=rep.role,
                        on_handoff=(
                            (lambda packet, r=rep:
                                self._dispatch_handoff(r, packet))
                            if rep.role == "prefill" else None),
                        claim=(lambda req, r=rep:
                               self._may_claim(r, req)),
                        device=rep.device)
        self._supervisor = None
        if supervise:
            sup = WorkerSupervisor(interval_s=supervisor_interval_s,
                                   max_restarts=worker_max_restarts,
                                   on_give_up=self._on_worker_give_up)
            for rep in self._replicas:
                sup.watch(
                    "replica%d" % rep.index,
                    should_run=lambda r=rep: (r.batcher.started
                                              and not r.batcher.stopping),
                    is_alive=lambda r=rep: r.batcher.alive,
                    restart=rep.batcher.restart,
                    fail_pending=self._fail_pending_if_all_dead)
                if rep.decoder is not None:
                    sup.watch(
                        "decode-replica%d" % rep.index,
                        should_run=lambda r=rep: (
                            r.decoder.started and not r.decoder.stopping),
                        is_alive=lambda r=rep: r.decoder.alive,
                        restart=lambda r=rep: self._revive_decoder(r),
                        fail_pending=lambda r=rep:
                            self._decode_fail_pending(r))
            self._supervisor = sup
        self._autoscaler_stop = threading.Event()
        self._autoscaler = None
        _pool_size_gauge.set(n)
        self._state = "ready"
        self._publish()
        if autostart:
            self.start()

    # -- lifecycle -----------------------------------------------------------
    def start(self):
        """Start (or revive) every replica worker.  Like the engine's
        ``start``, an operator call grants a fresh restart budget to any
        replica that comes back alive."""
        for rep in self._replicas:
            if not rep.batcher.alive:
                rep.batcher.start()
                if rep.batcher.alive:
                    rep.failed = False
                    if self._supervisor is not None:
                        self._supervisor.reset("replica%d" % rep.index)
            if rep.decoder is not None and not rep.decoder.alive:
                rep.decoder.start()
                if rep.decoder.alive:
                    rep.decode_failed = False
                    if self._supervisor is not None:
                        self._supervisor.reset(
                            "decode-replica%d" % rep.index)
        if self._supervisor is not None:
            self._supervisor.start()
        self._publish()
        return self

    def stop(self, drain=True, timeout=None):
        """Stop the pool.  ``drain=True`` answers everything queued first
        (every replica participates in the drain — gates open, including
        parked ones); new requests are rejected with ``ServingClosed``
        from the moment the stop begins.  Serializes with an in-flight
        rolling swap on the swap lock.

        A pool built on a SHARED queue (``queue=``) stops only its own
        consumers: it neither closes nor drains the queue (the sharing
        coordinator does, once every pool is stopped), and its drain
        waits on the shared watermark only via its own batchers' exit
        condition — close the shared queue BEFORE stopping the last
        pool or a drain-stop can block on sibling traffic."""
        with self._swap_lock:
            if self._state == "stopped":
                return
            self._state = "stopped"
            self.stop_autoscaler()
            if self._owns_queue:
                self._queue.close()
            if self._decode_queue is not None:
                self._decode_queue.close()
            for rep in self._replicas:
                # open every gate: the drain wants ALL warm capacity, and
                # a parked worker must observe `stopping` and exit
                rep.active = True
                rep.draining = False
                rep.force_serve = True
            if drain and self._owns_queue \
                    and (self._supervisor is not None
                         or any(r.batcher.alive
                                for r in self._replicas)):
                # drain POOL-level first, against the shared watermark:
                # per-batcher stop fails queue leftovers once ITS worker
                # is gone, which would shed requests the other replicas
                # were about to answer.  The supervisor is still running
                # here, so a replica dying mid-drain is restarted (or its
                # give-up tick fails the backlog) and the watermark
                # always lands; with neither a supervisor nor a live
                # worker the wait is skipped and the per-batcher stop
                # fails the leftovers instead.
                self._tracker.wait_for(self._queue.last_seq(), timeout)
            for rep in self._replicas:
                stopped = rep.batcher.stop(drain=drain, timeout=timeout)
                if stopped and rep.model is not None:
                    rep.model.close()
                # a wedged worker keeps its model open (same forced-
                # shutdown edge as the engine: never close an executable
                # under a running batch)
            if self._decode_enabled:
                # schedulers never close/drain the SHARED queue (they
                # don't own it) — stop them first, then fail whatever
                # no worker ever claimed
                for rep in self._replicas:
                    rep.decoder.stop(drain=drain, timeout=timeout)
                self._decode_queue.drain_remaining(
                    lambda r: ServingClosed("replica pool is stopped"))
                if self._sessions is not None:
                    # a stopped pool holds no sessions: release every
                    # pin (the workers are dead, so the release queues
                    # drain directly under each life lock) — a router
                    # cold-tier demotion must not leak pinned pages
                    self._sessions.clear()
                    for rep in self._replicas:
                        rep.decoder.drain_pending_releases()
            if self._supervisor is not None:
                self._supervisor.stop()
            if self._metrics_server is not None:
                self._metrics_server.stop()
                self._metrics_server = None
            self._queue.unregister_consumers(self._consumer_key)
            self._publish()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- worker failure ------------------------------------------------------
    def _on_worker_give_up(self, worker_name):
        if worker_name.startswith("decode-replica"):
            rep = self._replicas[int(worker_name[len("decode-replica"):])]
            rep.decode_failed = True
            self._publish()
            return
        idx = int(worker_name.replace("replica", ""))
        rep = self._replicas[idx]
        rep.failed = True
        # self-healing rotation: replace the lost capacity with a parked
        # warm replica when one exists (the autoscaler's budget still
        # bounds the rotation — this substitutes, it does not grow)
        if rep.active:
            for cand in self._replicas:
                if not cand.active and not cand.failed \
                        and cand.batcher.alive:
                    cand.active = True
                    self._emit_scale(len(self._active()), "replace_failed")
                    break
        self._publish()

    def _fail_pending_if_all_dead(self):
        """Supervisor give-up tick: only drain the SHARED queue once no
        replica can ever serve it — one dead replica must not fail
        requests its siblings will happily answer."""
        if any(r.batcher.alive and not r.failed for r in self._replicas):
            return
        if not self._owns_queue:
            # a sibling pool may still drain the shared queue; only the
            # sharing coordinator may declare it globally unservable
            return
        self._queue.drain_remaining(
            lambda r: ServingDegraded(
                "every pool replica is dead past its restart budget"),
            on_fail=lambda r: self._tracker.mark_done([r]))

    # -- durable decode (pool-routed generation) -----------------------------
    def _decode_ready(self, rep):
        """This replica's decoder can claim shared-queue work right now
        (the sibling side of the least-loaded comparison — state-only,
        never ``allow()``: probing a sibling's half-open breaker must
        not consume its probe slot)."""
        return (rep.active and not rep.draining and not rep.decode_failed
                and rep.decoder.alive
                and rep.decode_breaker.state != "open")

    def _decode_admissible(self, rep):
        """Could serve an admitted generation soon: not given-up and not
        breaker-open (a dead worker inside its restart budget counts —
        the supervisor revives it, and its in-flight journals replay on
        siblings meanwhile)."""
        return (rep.decoder is not None and not rep.decode_failed
                and rep.decode_breaker.state != "open")

    def _decode_claimable(self, rep):
        """:meth:`_decode_ready` AND allowed to claim fresh queue work:
        a pure decode-role replica serves handoff packets only (they
        are injected directly, never pulled from the queue)."""
        return rep.role != "decode" and self._decode_ready(rep)

    def _decode_gate(self, rep):
        """Claim gate for one replica's DecodeScheduler, consulted
        before every shared-queue pull (a parked HOL request is exempt
        — its prefix pages are pinned locally).

        Dispatch order of preference (the prefix-affinity policy, see
        serving/sessions.py): a queue head stamped with an affinity
        hint goes to its PREFERRED replica — every other replica defers
        while the hint is FRESH (within ``affinity_timeout_s``) and the
        target could still claim it; a stale or unservable hint is
        STRIPPED (``serving.affinity.fallbacks``) so the head can never
        wedge behind a dead, draining, breaker-open, or persistently
        full preference.  Unstamped (or stripped) heads fall back to
        least-loaded-by-free-slots: claim only when no claim-eligible
        sibling has MORE free seats; ties claim, so equal replicas race
        the queue and FIFO decides — no livelock."""
        if rep.force_serve and not rep.decode_failed:
            # pool stop-drain: every queued generation must reach a
            # terminal outcome NOW
            return True
        self._session_sweep()
        if (not rep.active or rep.draining or rep.decode_failed
                or not rep.decode_breaker.allow()):
            return False
        if rep.role == "decode":
            # pure decode replica: fresh prompts reach it only as
            # handoff packets from prefill-role siblings
            return False
        head = self._decode_queue.peek()
        aff = getattr(head, "affinity", None) if head is not None else None
        if aff is not None:
            if aff == rep.index:
                return True
            target = (self._replicas[aff]
                      if 0 <= aff < len(self._replicas) else None)
            fresh = (head.affinity_ts is not None
                     and (time.perf_counter() - head.affinity_ts
                          <= self._affinity_timeout_s))
            if (fresh and target is not None
                    and self._decode_claimable(target)):
                # the warm replica will claim it shortly: defer (it may
                # be momentarily full — a retirement frees a seat well
                # within the staleness window)
                return False
            # staleness bound: affinity never overrides health or
            # sustained overload — strip the hint, serve least-loaded
            head.affinity = None
            head.affinity_ts = None
            _affinity_fallbacks.inc()
        mine = rep.decoder.free_slots()
        others = [r.decoder.free_slots() for r in self._replicas
                  if r is not rep and self._decode_claimable(r)]
        return not others or mine >= max(others)

    def _may_claim(self, rep, req):
        """Per-POP claim check, run by the shared queue UNDER ITS LOCK
        against the head ``rep`` is about to pop.  The gate above is a
        peek-then-pop heuristic: two replicas can each approve their
        own momentary head, race the pop, and claim each other's
        affinity-tagged request — this predicate closes that window by
        deciding on the request actually being popped.  Fast and
        lock-free by contract: reads the hint + timestamp, strips a
        stale hint (same staleness bound as the gate — a hint never
        overrides liveness for long), refuses a fresh hint aimed
        elsewhere (the warm replica pops it instead)."""
        aff = getattr(req, "affinity", None)
        if aff is None or aff == rep.index:
            return True
        if (req.affinity_ts is not None
                and (time.perf_counter() - req.affinity_ts
                     <= self._affinity_timeout_s)):
            return False
        req.affinity = None
        req.affinity_ts = None
        _affinity_fallbacks.inc()
        return True

    def _session_sweep(self):
        """Time-gated TTL sweep of the session store, piggybacked on
        the decode gate (runs on whichever worker hits the gate next —
        no extra thread): expired sessions release their pins through
        the owning schedulers' release queues."""
        if self._sessions is None:
            return
        now = time.perf_counter()
        if now - self._session_sweep_ts < 1.0:
            return
        self._session_sweep_ts = now
        self._sessions.expire(now)

    def _dispatch_handoff(self, origin, packet):
        """Route one staged prefill->decode KV packet (roles mode) to
        the decode-capable replica with the most free seats — called on
        the ORIGIN (prefill) replica's worker thread by its scheduler's
        ``on_handoff`` hook.  Ready replicas are preferred, but a
        quiesced/draining one still accepts (injection is ungated: its
        worker seats packets even while it refuses fresh queue claims),
        so an autoscale park can never wedge an in-flight conversation.
        Returns True once a replica accepted the packet."""
        cands = [r for r in self._replicas
                 if r.role != "prefill" and r.decoder is not None
                 and not r.decode_failed]
        cands.sort(key=lambda r: (self._decode_ready(r),
                                  r.decoder.free_slots()), reverse=True)
        for r in cands:
            if r.decoder.inject_handoff(packet):
                if self._telemetry.recording:
                    self._telemetry.emit({
                        "type": "decode_handoff", "ts": time.time(),
                        "source": "serving", "seq": packet.req.seq,
                        "leg": "dispatch", "origin": origin.index,
                        "dest": r.index, "pages": packet.n_pages,
                    })
                return True
        return False

    def _revive_decoder(self, rep):
        """The supervisor's restart wrapper for one replica's decode
        worker: FIRST harvest the in-flight sequences (under the
        dead-worker proof — pages freed, journals intact), re-admit
        them so siblings pick them up immediately, THEN re-arm the
        thread.  The revived worker comes back with empty slots and the
        shared queue decides what it serves next."""
        for req in rep.decoder.evict_if_dead() or ():
            self._readmit_decode(req)
        return rep.decoder.restart()

    def _decode_fail_pending(self, rep):
        """Give-up tick for one replica's decode worker (dead past its
        restart budget): its in-flight sequences replay on siblings —
        durable decode means a lost replica loses NO generation — and
        the shared queue is drained typed only once no decoder could
        ever serve it."""
        for req in rep.decoder.evict_if_dead() or ():
            self._readmit_decode(req)
        if any(r.decoder.alive and not r.decode_failed
               for r in self._replicas):
            return
        self._decode_queue.drain_remaining(
            lambda r: ServingDegraded(
                "every pool decode replica is dead past its restart "
                "budget"))

    def _readmit_decode(self, req):
        """Re-admit one harvested generation: rewrite the request to
        resume from its journal (``prompt + accepted`` re-prefilled,
        the remaining cap as the new budget — bitwise-identical
        continuation via absolute-position PRNG folding) and re-enqueue
        it, counting against ``DecodeConfig.replay_budget``."""
        if req.done():
            return
        j = req.journal
        if j.remaining() <= 0:
            # every token was already accepted when the replica died —
            # nothing to replay, the journal IS the answer
            req.complete(j.tokens())
            return
        if j.replays >= self._decode_config.replay_budget:
            req.fail(ServingDegraded(
                "replica died mid-decode and the replay budget (%d) is "
                "spent after %d/%d tokens"
                % (self._decode_config.replay_budget, len(j.accepted),
                   j.max_new0)))
            return
        j.replays += 1
        _decode_replays.inc()
        req.prompt = j.resume_prompt()
        req.max_new_tokens = j.remaining()
        # the old hint likely points at the replica that just died —
        # re-stamp against live state (warm prefix pages that survived
        # elsewhere still attract the replay; a dead target would only
        # stall the queue head until the staleness bound strips it)
        req.affinity = None
        req.affinity_ts = None
        if self._affinity_timeout_s > 0:
            self._stamp_affinity(req)
        if self._telemetry.recording:
            self._telemetry.emit({
                "type": "decode_replay", "ts": time.time(),
                "source": "serving", "seq": req.seq,
                "accepted": len(j.accepted), "remaining": j.remaining(),
                "replays": j.replays,
            })
        try:
            # re-put re-runs admission (a fresh seq, deadline-aware
            # shed against the ORIGINAL absolute deadline): a doomed or
            # over-capacity replay fails typed here instead of hanging
            self._decode_queue.put(req)
        except ServingError as exc:
            req.fail(exc)

    # -- introspection -------------------------------------------------------
    def _active(self):
        return [r for r in self._replicas if r.active]

    def _ready(self):
        return [r for r in self._replicas if r.ready()]

    def active_replicas(self):
        """Rotation size (autoscale's unit): replicas currently allowed
        to claim work (draining/ejected/dead ones still count toward the
        rotation — they are impaired, not descaled)."""
        return len(self._active())

    def ready_replicas(self):
        """Replicas able to claim work RIGHT NOW (active, not draining,
        worker alive, breaker not open).  The rolling-swap invariant the
        gate asserts: this never reaches 0 during a swap of a >=2
        replica pool."""
        return len(self._ready())

    @property
    def replicas(self):
        return len(self._replicas)

    @property
    def state(self):
        """"ready" | "degraded" | "swapping" | "stopped" — ``degraded``
        is derived: lifecycle-ready but at least one IN-ROTATION replica
        is impaired (dead worker past budget or breaker open)."""
        if self._state == "ready":
            if any(r.failed or r.breaker.state == "open"
                   for r in self._active()):
                return "degraded"
        return self._state

    def ready(self):
        """Load-balancer truth: at least one replica serves (or provably
        will within the supervisor's restart budget)."""
        if self._state not in ("ready", "swapping"):
            return False
        if any(r.admissible() for r in self._replicas):
            return True
        # decode-only pool (model_dir=None): the predict side never
        # becomes admissible, the decode side is what serves
        return self._decode_enabled and any(
            self._decode_admissible(r) for r in self._replicas)

    def replica_stats(self):
        return [r.stats() for r in self._replicas]

    def health(self):
        self._publish()
        versions = sorted({r.model.version for r in self._replicas
                           if r.model is not None})
        h = {
            "state": self.state,
            "ready": self.ready(),
            "replicas": len(self._replicas),
            "active_replicas": self.active_replicas(),
            "ready_replicas": self.ready_replicas(),
            "min_replicas": self.min_replicas,
            "max_replicas": self.max_replicas,
            # one version in steady state; two mid-rolling-swap (or after
            # a failed swap left the pool mixed — retry completes it)
            "model_versions": versions,
            "model_version": versions[-1] if versions else None,
            "batch_buckets": list(self.batch_buckets),
            "max_batch_size": self.max_batch_size,
            "queue_depth": self._queue.depth(),
            "queue_capacity": self._queue.capacity,
            "class_depths": self._queue.class_depths(),
            "class_rows": self._queue.class_rows(),
            "service_rate_rows_per_s": self._queue.service_rate,
            "requests": self._queue.last_seq(),
            "batches": sum(r.batcher.batches for r in self._replicas),
            "per_replica": self.replica_stats(),
        }
        if self._decode_enabled:
            h["decode"] = {
                "queue_depth": self._decode_queue.depth(),
                "admitted": self._decode_queue.last_seq(),
                "ready_replicas": sum(1 for r in self._replicas
                                      if self._decode_ready(r)),
                "roles": [r.role for r in self._replicas],
            }
            if self._sessions is not None:
                h["decode"]["sessions"] = self._sessions.stats()
        if self._supervisor is not None:
            h["workers"] = self._supervisor.stats()
        return h

    def serve_metrics(self, host="127.0.0.1", port=0):
        """Live ``/metrics`` + ``/healthz`` endpoint for the POOL (same
        contract as the engine's): healthz serves :meth:`health` and
        answers 503 while :meth:`ready` is False."""
        srv = self._metrics_server
        if srv is not None and srv.running:
            return srv
        self._metrics_server = _obs.MetricsServer(
            host=host, port=port, health_fn=self.health).start()
        return self._metrics_server

    @property
    def feed_names(self):
        m = self._spec_model()
        return [] if m is None else list(m.feed_names)

    @property
    def fetch_names(self):
        m = self._spec_model()
        return [] if m is None else list(m.fetch_names)

    @property
    def model_version(self):
        versions = [r.model.version for r in self._replicas
                    if r.model is not None]
        return max(versions) if versions else None

    def _spec_model(self):
        for rep in self._replicas:
            m = rep._current_model()
            if m is not None:
                return m
        return None

    def _publish(self):
        _active_gauge.set(len(self._active()))
        _ready_gauge.set(len(self._ready()))
        for rep in self._replicas:
            rep.publish()

    # -- request admission ---------------------------------------------------
    def predict_async(self, feed, deadline_ms=None, priority=None,
                      tenant=None):
        """Admit one request into the SHARED queue; whichever ready
        replica claims it serves it.  Same error contract as the
        engine's ``predict_async``; ``ServingDegraded`` only when no
        replica could ever serve it (all dead past budget or ejected).
        ``tenant`` (plus the pool's ``model_label``) stamps the request
        for the labeled per-class accounting — quota enforcement itself
        lives in the router, not here."""
        if self._state == "stopped":
            raise ServingClosed("replica pool is stopped")
        if self._state == "loading":
            raise ServingClosed("replica pool is still loading")
        spec_model = self._spec_model()
        if spec_model is None:
            raise ServingError("replica pool has no loaded model")
        if not any(r.admissible() for r in self._replicas):
            raise ServingDegraded(
                "no replica can serve: all dead past restart budget or "
                "circuit-broken; pool degraded")
        arrays, rows = normalize_feed(spec_model, feed, self.max_batch_size)
        if priority is not None and priority not in PRIORITY_CLASSES:
            raise ServingError("unknown priority class %r (know %s)"
                               % (priority, PRIORITY_CLASSES))
        ms = deadline_ms if deadline_ms is not None \
            else self.default_deadline_ms
        deadline = None if ms is None else time.perf_counter() + ms / 1e3
        req = self._queue.put(
            Request(arrays, rows, deadline=deadline, priority=priority,
                    tenant=tenant, model=self.model_label))
        _requests.inc()
        return req

    def predict(self, feed, deadline_ms=None, priority=None, timeout=None,
                tenant=None):
        return self.predict_async(
            feed, deadline_ms=deadline_ms, priority=priority,
            tenant=tenant).result(timeout=timeout)

    def generate_async(self, prompt, max_new_tokens=None, deadline_ms=None,
                       priority=None, temperature=None, seed=None,
                       tenant=None, session=None):
        """Admit one generation into the SHARED decode queue; whichever
        least-loaded decode-ready replica claims it serves it — and if
        that replica dies mid-decode, the journal replays the sequence
        on a sibling bitwise-identically (see the module docstring).
        Same per-request knobs as
        :meth:`~.decode_scheduler.DecodeScheduler.submit`; a seedless
        request gets a pool-pinned admission-order seed (stable across
        replays).  Requires ``decode_model=`` at construction."""
        if not self._decode_enabled:
            raise ServingError(
                "pool has no decode model (pass decode_model= at "
                "construction)")
        if self._state == "stopped":
            raise ServingClosed("replica pool is stopped")
        if self._state == "loading":
            raise ServingClosed("replica pool is still loading")
        if not any(self._decode_admissible(r) for r in self._replicas):
            raise ServingDegraded(
                "no replica can decode: all dead past restart budget or "
                "circuit-broken; pool degraded")
        dcfg = self._decode_config
        tokens = np.asarray(prompt)
        if tokens.ndim != 1 or tokens.shape[0] < 1:
            raise ServingError(
                "prompt must be a non-empty 1-D token array, got shape %s"
                % (tokens.shape,))
        tokens = tokens.astype(np.int32, copy=False)
        n_new = int(dcfg.max_new_tokens if max_new_tokens is None
                    else max_new_tokens)
        if n_new < 1:
            raise ServingError("max_new_tokens must be >= 1")
        buckets = self._replicas[0].decoder.prefill_buckets
        plen = int(tokens.shape[0])
        if plen > buckets[-1]:
            raise ServingError(
                "prompt length %d exceeds the largest prefill bucket %d"
                % (plen, buckets[-1]))
        if plen + n_new > dcfg.max_seq_len:
            raise ServingError(
                "prompt %d + max_new_tokens %d exceeds max_seq_len %d"
                % (plen, n_new, dcfg.max_seq_len))
        if temperature is not None and float(temperature) < 0:
            raise ServingError("temperature must be >= 0, got %r"
                               % (temperature,))
        if priority is not None and priority not in PRIORITY_CLASSES:
            raise ServingError("unknown priority class %r (know %s)"
                               % (priority, PRIORITY_CLASSES))
        if seed is None:
            with self._decode_seed_lock:
                seed = self._decode_admissions
                self._decode_admissions += 1
        ms = deadline_ms if deadline_ms is not None \
            else dcfg.default_deadline_ms
        deadline = None if ms is None else time.perf_counter() + ms / 1e3
        greq = GenerateRequest(tokens, n_new, deadline=deadline,
                               priority=priority, temperature=temperature,
                               seed=seed, session=session)
        # stamp the accounting labels BEFORE put: the admission raise
        # paths read them for the labeled rejected counters
        greq.tenant = tenant
        greq.model = self.model_label
        if self._affinity_timeout_s > 0:
            self._stamp_affinity(greq)
        req = self._decode_queue.put(greq)
        _decode_requests.inc()
        return req

    def _stamp_affinity(self, req):
        """Stamp the admission-time placement hint: the session's
        sticky replica first (where its pinned pages live), the replica
        with the LONGEST warm prefix of this prompt second (read-only
        chain-hash peek per claim-eligible replica — hashes computed
        once), no hint otherwise.  Best-effort by design: the peek
        races worker-side cache mutation, and a wrong hint only costs
        placement (the gate's staleness bound strips it)."""
        pref = None
        if self._sessions is not None and req.session is not None:
            rec = self._sessions.get(req.session)
            if rec is not None:
                target = (self._replicas[rec.replica]
                          if 0 <= rec.replica < len(self._replicas)
                          else None)
                if target is not None and self._decode_claimable(target):
                    pref = rec.replica
                    _affinity_sticky.inc()
                elif target is not None:
                    # the sticky replica exists but is draining, parked,
                    # breaker-open, or dead: health overrides affinity —
                    # count the abandoned preference and fall through to
                    # prefix-match / least-loaded
                    _affinity_fallbacks.inc()
        if pref is None and self._decode_config.prefix_cache:
            hashes = self._replicas[0].decoder._cache.prefix_hashes(
                req.prompt)
            if hashes:
                best, best_n = None, 0
                for r in self._replicas:
                    if not self._decode_claimable(r):
                        continue
                    n = r.decoder._cache.peek_hashes(hashes)
                    if n > best_n:
                        best, best_n = r.index, n
                if best is not None:
                    pref = best
                    _affinity_prefix.inc()
        if pref is None:
            _affinity_none.inc()
            return
        req.affinity = pref
        req.affinity_ts = time.perf_counter()

    def end_session(self, session):
        """Explicitly finish a conversation: drop its store record and
        release its pinned pages (freed on the owning replica's worker
        at its next iteration).  True when the session existed."""
        if self._sessions is None:
            return False
        return self._sessions.end_session(session)

    @property
    def sessions(self):
        """The pool's :class:`~.sessions.SessionStore` (None when
        sessions are disabled)."""
        return self._sessions

    def generate(self, prompt, max_new_tokens=None, deadline_ms=None,
                 timeout=None, priority=None, temperature=None, seed=None,
                 tenant=None, session=None):
        """Synchronous generate: the generated int32 token ids."""
        return self.generate_async(
            prompt, max_new_tokens=max_new_tokens, deadline_ms=deadline_ms,
            priority=priority, temperature=temperature,
            seed=seed, tenant=tenant,
            session=session).result(timeout=timeout)

    def drain_decode(self, timeout=None):
        """Block until no generation is queued, parked, or decoding
        anywhere in the pool.  False on timeout."""
        deadline = (None if timeout is None
                    else time.perf_counter() + timeout)
        while True:
            if self._decode_queue is None or (
                    self._decode_queue.depth() == 0
                    and all(r.decoder.idle() for r in self._replicas)):
                return True
            if deadline is not None and time.perf_counter() >= deadline:
                return False
            time.sleep(0.005)

    def drain(self, timeout=None):
        """Block until everything admitted so far has reached a terminal
        outcome (the pool-wide exact watermark).  False on timeout."""
        return self._tracker.wait_for(self._queue.last_seq(), timeout)

    # -- rolling hot swap ----------------------------------------------------
    def swap_model(self, model_dir, backend="auto", drain_timeout_s=60.0):
        """ROLLING hot swap: for each replica in turn — load + warm the
        new version on ITS device while every other replica keeps
        serving, close the replica's gate, wait until it provably has no
        dispatch in flight, flip, reopen.  Capacity never reaches zero
        for a >=2 replica pool: exactly one replica is ever out, and in
        a PARTIAL rotation (autoscale parked the rest) a parked warm
        sibling is temporarily opened as cover while the sole ready
        replica drains.

        Version semantics: a request finishes on the version of the
        replica that claimed it, so requests in flight across the swap
        may be answered by either version — each answer is a complete
        output of exactly one version.  If a replica's drain times out
        the swap raises, leaving earlier replicas on the new version
        and later ones on the old (pool reports both in
        ``health()["model_versions"]``); re-running the swap completes
        the rollout.  Returns the new version number."""
        if self._state == "stopped":
            raise ServingClosed("replica pool is stopped")
        with self._swap_lock:
            if self._state == "stopped":   # stop() won the lock first
                raise ServingClosed("replica pool is stopped")
            prev_state, self._state = self._state, "swapping"
            new_version = None
            try:
                for rep in self._replicas:
                    new = rep.load_model(model_dir, backend)
                    ref = self._spec_model()
                    # in-flight requests were normalized against the
                    # serving specs; the new version must accept exactly
                    # the same feeds or they could poison on it
                    if (new.feed_names != ref.feed_names
                            or new.feed_specs != ref.feed_specs):
                        new.close()
                        raise ServingError(
                            "swap rejected: new model feeds %s %s != "
                            "serving feeds %s %s"
                            % (new.feed_names, new.feed_specs,
                               ref.feed_names, ref.feed_specs))
                    if self._warmup:
                        new.warmup(self.batch_buckets)
                    # partial rotation (autoscale parked the rest):
                    # draining the SOLE ready replica would zero serving
                    # capacity even though warm siblings sit parked —
                    # open one as cover for this replica's drain window,
                    # and park it again after (net rotation unchanged)
                    cover = None
                    if rep.ready() and not any(
                            o.ready() for o in self._replicas
                            if o is not rep):
                        for cand in self._replicas:
                            if (cand is not rep and not cand.active
                                    and cand.admissible()
                                    and cand.batcher.alive):
                                cand.active = True
                                cover = cand
                                break
                    # close the gate FIRST, then stamp: a park observed
                    # after `since` was necessarily a park at a closed
                    # gate, so the single-threaded worker cannot start
                    # another dispatch until the drain flag clears
                    rep.draining = True
                    since = time.perf_counter()
                    self._publish()
                    try:
                        if not rep.wait_quiescent(since, drain_timeout_s):
                            new.close()
                            raise ServingError(
                                "rolling swap: replica %d drain timed out "
                                "after %.1fs (%d rows in flight)"
                                % (rep.index, drain_timeout_s,
                                   rep.inflight_rows))
                        with rep.model_lock:
                            old, rep.model = rep.model, new
                    finally:
                        rep.draining = False
                        if cover is not None:
                            cover.active = False
                        self._publish()
                    # the replica was parked at a closed gate when we
                    # flipped: the old version is idle — safe to close
                    old.close()
                    new_version = new.version
                    _replica_swapped.inc()
                    if self._telemetry.recording:
                        self._telemetry.emit({
                            "type": "replica_swap", "ts": time.time(),
                            "source": "serving", "replica": rep.index,
                            "from_version": old.version,
                            "to_version": new.version,
                            "ready_replicas": self.ready_replicas(),
                        })
            finally:
                self._state = prev_state
        _swaps.inc()
        if self._telemetry.recording:
            self._telemetry.emit({
                "type": "model_swap", "ts": time.time(), "source": "serving",
                "rolling": True, "replicas": len(self._replicas),
                "to_version": new_version, "model_dir": model_dir,
            })
        return new_version

    # -- autoscale -----------------------------------------------------------
    def set_active_replicas(self, n, reason="manual"):
        """Resize the rotation to ``n`` (clamped to
        ``[min_replicas, max_replicas]``): activate parked replicas in
        index order, or quiesce active ones (stop claiming, let
        in-flight work finish, park warm — their model, device params,
        and compiled buckets stay resident).  Health-aware on both
        sides: scale-up counts only HEALTHY (non-failed) actives toward
        the target, so a dead-past-budget replica in the rotation is
        backfilled by a parked spare instead of silently shrinking
        capacity; scale-down parks failed actives first, then draining
        ones (already not claiming), then the highest-index healthy —
        quiescing must never park the last healthy replica while a dead
        one squats in the rotation.  Returns the applied rotation
        size."""
        with self._scale_lock:
            n = max(self.min_replicas, min(int(n), self.max_replicas))
            before = len(self._active())
            healthy = sum(1 for r in self._active() if not r.failed)
            if n > healthy:
                want = n - healthy
                grew = False
                for rep in self._replicas:
                    if want == 0:
                        break
                    if not rep.active and not rep.failed:
                        rep.active = True
                        grew = True
                        want -= 1
                if grew:
                    _scale_ups.inc()
            active = self._active()
            if n < len(active):
                excess = len(active) - n
                # park the impaired first (dead past budget, breaker
                # open, mid-drain — none of them is claiming anyway),
                # then the highest-index healthy: quiescing must never
                # park serving capacity while impaired replicas squat
                impaired = [r for r in active
                            if r.failed or r.breaker.state == "open"
                            or r.draining]
                victims = impaired + [r for r in reversed(active)
                                      if r not in impaired]
                for rep in victims[:excess]:
                    rep.active = False
                _scale_downs.inc()
            now_active = len(self._active())
            self._publish()
            if now_active != before:
                self._emit_scale(now_active, reason, before=before)
            return now_active

    def _emit_scale(self, to_n, reason, before=None):
        if self._telemetry.recording:
            self._telemetry.emit({
                "type": "replica_scale", "ts": time.time(),
                "source": "serving", "from": before, "to": to_n,
                "reason": reason, "ready_replicas": self.ready_replicas(),
            })

    def autoscale_tick(self, desired=None, now=None):
        """Apply one autoscale decision.  ``desired`` defaults to the
        live ``serving.autoscale.desired_replicas`` gauge (published by
        :class:`~paddle_tpu.observability.SLOMonitor.evaluate`).
        Scale-UP applies immediately; scale-DOWN only once desired has
        stayed below the active count for ``scale_down_after_s``
        straight (one recovered window must not thrash the rotation),
        and then only to the HIGHEST desired seen inside that window.
        Returns the rotation size after the tick."""
        if desired is None:
            v = _obs.gauge("serving.autoscale.desired_replicas").value
            if v is None:
                return self.active_replicas()
            desired = v
        desired = max(self.min_replicas,
                      min(int(desired), self.max_replicas))
        now = time.perf_counter() if now is None else now
        active = self.active_replicas()
        if desired > active:
            self._below_since = None
            return self.set_active_replicas(desired, reason="autoscale_up")
        if desired < active:
            if self._below_since is None:
                self._below_since = now
                self._below_peak = desired
            else:
                self._below_peak = max(self._below_peak, desired)
            if now - self._below_since >= self.scale_down_after_s:
                target = self._below_peak
                self._below_since = None
                return self.set_active_replicas(
                    target, reason="autoscale_down")
            return active
        self._below_since = None
        return active

    def start_autoscaler(self, monitor=None, interval_s=None,
                         metrics_url=None, metric=None, prefix="paddle_tpu_",
                         scrape_timeout_s=2.0):
        """Run the autoscale loop on a daemon thread: each tick either
        evaluates ``monitor`` (an
        :class:`~paddle_tpu.observability.SLOMonitor`, typically
        constructed with ``engine=pool``), scrapes ``metrics_url``, or —
        with neither — consumes the latest published gauge value.

        ``metrics_url`` drives sizing from a LIVE Prometheus-text scrape
        (any ``/metrics`` endpoint — this pool's own
        :meth:`serve_metrics`, another process's exporter, or a
        Prometheus federation proxy), decoupling the autoscaler from an
        in-process :class:`SLOMonitor`: the monitor can run wherever the
        metrics live.  Each tick fetches the exposition, parses it with
        :func:`~paddle_tpu.observability.parse_prometheus` in lenient
        mode (a third-party exporter's exotic lines are skipped, not
        fatal), and applies the ``serving.autoscale.desired_replicas``
        sample (spelled ``<prefix>serving_autoscale_desired_replicas``;
        override the exact sample name with ``metric``).  A failed
        scrape (or raising monitor) skips that tick — sizing must
        outlive a flaky exporter — counting on
        ``serving.autoscale.tick_errors``, and an absent sample counts
        on ``serving.autoscale.scrape_misses``, so an inert wiring (bad
        URL, mistyped metric name) is visible to the operator instead
        of silently idling."""
        if monitor is not None and metrics_url is not None:
            raise ValueError("pass monitor= or metrics_url=, not both")
        if self._autoscaler is not None and self._autoscaler.is_alive():
            return self
        period = float(interval_s) if interval_s is not None else (
            monitor.window_s if monitor is not None else 1.0)
        scrape_name = None
        if metrics_url is not None:
            from ..observability.export import parse_prometheus, \
                prometheus_name

            scrape_name = metric or prometheus_name(
                "serving.autoscale.desired_replicas", prefix)

            def scrape_desired():
                import urllib.request

                with urllib.request.urlopen(
                        metrics_url, timeout=scrape_timeout_s) as resp:
                    body = resp.read().decode("utf-8", "replace")
                v = parse_prometheus(body, strict=False).get(scrape_name)
                return None if v is None else int(round(v))

        self._autoscaler_stop.clear()

        def loop():
            while not self._autoscaler_stop.wait(period):
                try:
                    desired = None
                    if monitor is not None:
                        desired = monitor.evaluate()["desired_replicas"]
                    elif scrape_name is not None:
                        desired = scrape_desired()
                        if desired is None:
                            # sample absent: not a decision — but leave
                            # a trail, or a mistyped metric name would
                            # look exactly like a healthy idle loop
                            _obs.inc("serving.autoscale.scrape_misses")
                            continue
                    self.autoscale_tick(desired)
                except Exception:
                    # scaling must outlive a flaky health probe /
                    # exporter; the counter keeps it from failing silent
                    _obs.inc("serving.autoscale.tick_errors")

        self._autoscaler = threading.Thread(
            target=loop, name="paddle-tpu-replica-autoscaler", daemon=True)
        self._autoscaler.start()
        return self

    def stop_autoscaler(self, timeout=2.0):
        self._autoscaler_stop.set()
        t = self._autoscaler
        if t is not None and t.is_alive():
            t.join(timeout)
        self._autoscaler = None
