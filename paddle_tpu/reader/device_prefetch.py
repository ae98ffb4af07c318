"""Asynchronous device-feed pipeline: overlap host->device transfer with
compute.

The reference Fluid kept the accelerator fed with ``py_reader`` /
double-buffered ``data_feeder`` queues (python/paddle/fluid/layers/io.py's
double_buffer decorator + the C++ buffered readers).  The TPU analog here
is host-side: a background thread runs DataFeeder conversion AND
``jax.device_put`` into a bounded double/triple buffer, so batch N+1 is
converting/transferring while the compiled step for batch N runs on
device.  The executor's fast path (executor._BoundProgram) then accepts
the already-committed device arrays without any per-step host work — the
feed plan's shape/dtype check is all that remains on the critical path.

Placement matches what the compiled step wants, so jit never re-copies:

- mesh attached (ParallelExecutor / Trainer(parallel=...)): each feed is
  placed with the SAME ``NamedSharding`` the runner bakes into its
  ``in_shardings`` (``Executor.plan_feed_shardings`` — batch-sharded on
  ``dp`` for declared data vars, replicated otherwise);
- no mesh: committed to the executor's device.

Shutdown discipline is shared with ``reader.decorator``: abandoning the
generator (break / exception / GeneratorExit) cancels the producer
thread(s), drains the buffer, and closes the source reader — no pump
thread is ever left blocked on a full queue (see
``decorator._shutdown_pump``).

Interaction with the fault-tolerant runtime (PR 2): the pipeline only
converts and transfers feeds — parameters never flow through it — so
nan_guard's rewind, ``retry_reader`` resume, and FailureMonitor's
checkpoint-then-stop all stay correct with batches in flight; an
abandoned loop tears the buffer down via the shared shutdown path.

Usage::

    feeder = fluid.DataFeeder(feed_list=[x, y], place=place)
    batches = fluid.reader.device_prefetch.decorate_device_feed(
        train_reader, feeder, exe, main_program)
    for feed in batches():            # feed values are committed jax arrays
        exe.run(main_program, feed=feed, fetch_list=[loss])

``Trainer.train``/``Trainer.test`` route readers through this
automatically (opt out with ``prefetch=False`` or
``PADDLE_TPU_DEVICE_PREFETCH=0``).
"""
from __future__ import annotations

import os
import queue as _queue
import threading
import weakref

import numpy as np

from .. import observability as _obs
from ..core import np_dtype
from .decorator import _STOP, _Failure, _cancellable_put, _shutdown_pump

__all__ = [
    "DevicePrefetcher",
    "decorate_device_feed",
    "device_feed_reader",
    "put_feed_on_device",
    "shard_feed_list",
    "prefetch_enabled_default",
    "transfer_count",
]


# host->device feed transfers issued by this module: a telemetry-registry
# counter (its internal lock covers transfer_threads > 1), the same cell
# executor step records report as ``prefetch_transfers``
_transfers = _obs.counter("prefetch.transfer")


def transfer_count():
    """Total ``device_put`` transfers this module has issued — bench/test
    instrumentation for the zero-copy contract (a training loop fed by
    the prefetcher must transfer each batch exactly once).  A view of
    the ``prefetch.transfer`` telemetry counter."""
    return _transfers.value


def _device_put(value, placement):
    from ..core import safe_import_jax

    jax = safe_import_jax()
    _transfers.inc()
    with _obs.span("prefetch.device_put"):
        if placement is None:
            return jax.device_put(value)
        return jax.device_put(value, placement)


def prefetch_enabled_default():
    """Process-wide default for Trainer's automatic prefetch routing;
    ``PADDLE_TPU_DEVICE_PREFETCH=0`` is the opt-out killswitch."""
    return os.environ.get("PADDLE_TPU_DEVICE_PREFETCH", "1") != "0"


def _declared_dtype(block, name):
    if not block.has_var(name):
        return None
    want = block.var(name).dtype
    return np_dtype(want) if want is not None else None


def _place_feed(feed, executor, program, shardings):
    """One host feed dict -> committed device arrays.  Non-plain entries
    (LoDArray, (array, lengths) tuples, values already on device) pass
    through untouched — the executor's slow path owns their conversion."""
    block = program.global_block()
    default_place = None if executor is None else executor.place.jax_device()
    out = {}
    for name, val in feed.items():
        if not isinstance(val, (np.ndarray, np.generic)):
            out[name] = val
            continue
        want = _declared_dtype(block, name)
        if want is not None and val.dtype != want:
            # cast on host while OFF the critical path, so the bound feed
            # plan sees the final dtype and the step-loop cast disappears
            val = np.asarray(val).astype(want, copy=False)
        placement = shardings.get(name) if shardings else default_place
        out[name] = _device_put(val, placement)
    return out


def put_feed_on_device(feed, executor, program=None):
    """Convert one feed dict's plain ndarrays into committed jax arrays
    placed the way ``executor``'s compiled step wants them (NamedSharding
    under an attached mesh, the executor's device otherwise).  The
    one-shot form of the pipeline below — same placement logic, no
    background thread."""
    from ..framework import default_main_program

    program = program or default_main_program()
    shardings = executor.plan_feed_shardings(program, feed)
    return _place_feed(feed, executor, program, shardings)


def shard_feed_list(feed_list, mesh, data_names, program=None):
    """Per-device feed dicts -> ONE global feed dict without a host-side
    batch concatenation.

    For a 1-D ``("dp",)`` mesh whose size matches ``len(feed_list)``,
    each declared data var's shard is ``device_put`` straight to its
    device and the global array is stitched with
    ``jax.make_array_from_single_device_arrays`` — no full-batch host
    copy, and XLA never has to re-split what the host just concatenated.
    Everything else (replicated vars, ragged shards, foreign meshes)
    falls back to concatenation, skipping the copy entirely for a
    single-entry list."""
    from ..core import safe_import_jax

    jax = safe_import_jax()
    from jax.sharding import NamedSharding, PartitionSpec as P

    per_key = {}
    for d in feed_list:
        for k, v in d.items():
            per_key.setdefault(k, []).append(v)

    sharded_ok = (
        mesh is not None
        and mesh.devices.ndim == 1
        and mesh.axis_names[0] == "dp"
        and mesh.devices.size == len(feed_list)
    )
    devices = list(mesh.devices.ravel()) if mesh is not None else []
    block = program.global_block() if program is not None else None
    out = {}
    for k, vals in per_key.items():
        shapes = {tuple(np.shape(v)) for v in vals}
        dtypes = {np.asarray(v).dtype if not hasattr(v, "dtype") else v.dtype
                  for v in vals}
        if (sharded_ok and k in data_names and len(vals) == len(devices)
                and len(shapes) == 1 and len(dtypes) == 1
                and np.ndim(vals[0]) >= 1
                and all(isinstance(v, (np.ndarray, np.generic)) for v in vals)):
            want = _declared_dtype(block, k) if block is not None else None
            shard_shape = shapes.pop()
            shards = []
            for v, dev in zip(vals, devices):
                if want is not None and v.dtype != want:
                    v = v.astype(want, copy=False)
                shards.append(_device_put(v, dev))
            global_shape = (len(shards) * shard_shape[0],) + shard_shape[1:]
            out[k] = jax.make_array_from_single_device_arrays(
                global_shape, NamedSharding(mesh, P("dp")), shards)
        elif len(vals) == 1:
            out[k] = vals[0]  # nothing to merge: keep the caller's array
        else:
            out[k] = np.concatenate([np.asarray(v) for v in vals], axis=0)
    return out


def _feed_pump(source, transform, src_lock, q, stop):
    """Worker loop shared by a DevicePrefetcher's transfer thread(s):
    pull the next item from the (lock-serialized) source, transform it —
    conversion + device_put, unlocked, so transfers pipeline — and post
    it.  Module-level on purpose: it must not close over the prefetcher
    instance (see DevicePrefetcher.__init__)."""
    try:
        while not stop.is_set():
            try:
                with src_lock:
                    item = next(source)
            except StopIteration:
                break
            if transform is not None:
                # the span makes conversion+transfer visible per-batch on
                # the producer thread's trace track, so Perfetto shows it
                # overlapping the main thread's dispatch spans
                with _obs.span("prefetch.convert_transfer"):
                    item = transform(item)
            if not _cancellable_put(q, item, stop):
                return
    except BaseException as e:  # noqa: BLE001 — forwarded, not swallowed
        _cancellable_put(q, _Failure(e), stop)
        return
    _cancellable_put(q, _STOP, stop)


class DevicePrefetcher:
    """Bounded async queue of on-device feed dicts.

    ``source`` is an iterator (typically ``reader()``); ``transform`` maps
    each item to the queued value — for the standard pipeline that is
    DataFeeder conversion + ``device_put`` — and runs on the background
    thread(s), off the step loop's critical path.

    ``buffer_size`` bounds device memory held by in-flight batches
    (2 = double buffer, 3 = triple).  ``transfer_threads > 1`` pipelines
    several transfers concurrently — the regime where each transfer is
    latency-bound rather than bandwidth-bound — at the cost of
    DELIVERY ORDER: multi-threaded delivery is whichever transfer
    finishes first, so keep the default of 1 for training loops that
    need determinism.

    Iterate it, or use :func:`decorate_device_feed` for the
    reader-creator form.  ``close()`` (also called on exhaustion and by
    the creator's ``finally``) cancels the producers, drains the queue,
    and closes the source iterator via the shared
    ``decorator._shutdown_pump`` path.
    """

    def __init__(self, source, transform=None, buffer_size=2,
                 transfer_threads=1):
        self._source = source
        self._q = _queue.Queue(maxsize=max(int(buffer_size), 1))
        self._stop = threading.Event()
        self._live = max(int(transfer_threads), 1)
        self._closed = False
        # the workers must NOT hold a reference to self (a bound-method
        # target would pin the instance alive for as long as the thread
        # runs, so an abandoned-without-close() prefetcher could never be
        # collected); they get the shared pieces directly, and a GC
        # finalizer then covers the no-close() path — stop, drain, join,
        # exactly the teardown close() performs
        src_lock = threading.Lock()
        self._threads = [
            threading.Thread(
                target=_feed_pump,
                args=(source, transform, src_lock, self._q, self._stop),
                name="paddle-tpu-device-prefetch", daemon=True)
            for _ in range(self._live)
        ]
        self._finalizer = weakref.finalize(
            self, _shutdown_pump, self._q, self._threads, self._stop)
        for t in self._threads:
            t.start()

    # -- consumer ------------------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        if self._closed:
            raise StopIteration
        while True:
            # the time the step loop actually spent blocked on input:
            # the ``prefetch.wait`` cell, always on (a near-zero mean is
            # a pipeline that keeps ahead of the step loop)
            with _obs.span("prefetch.wait"):
                item = self._q.get()
            if item is _STOP:
                self._live -= 1
                if self._live > 0:
                    continue  # other transfer threads still draining
                self.close()
                raise StopIteration
            if isinstance(item, _Failure):
                self.close()
                raise item.exc
            return item

    def close(self):
        """Idempotent teardown: cancel producers, drain, join, and close
        the source iterator so the underlying reader is released."""
        if self._closed:
            return
        self._closed = True
        self._finalizer.detach()  # explicit close supersedes the GC hook
        _shutdown_pump(self._q, self._threads, self._stop)
        if not any(t.is_alive() for t in self._threads):
            close = getattr(self._source, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:
                    pass


def device_feed_reader(feed_reader, executor, program=None, buffer_size=2,
                       transfer_threads=1):
    """Wrap a reader of HOST feed dicts into a creator of generators that
    yield ON-DEVICE feed dicts, transfers running on background thread(s).
    For raw sample-batch readers use :func:`decorate_device_feed`, which
    also moves DataFeeder conversion off the step loop."""
    from ..framework import default_main_program

    def prefetching():
        prog = program or default_main_program()
        cache = {}  # feed-signature -> shardings: resolved once, reused

        def place(feed):
            sig = tuple(sorted(
                (n, tuple(np.shape(v))) for n, v in feed.items()
                if isinstance(v, (np.ndarray, np.generic))))
            shardings = cache.get(sig)
            if shardings is None and sig not in cache:
                shardings = cache[sig] = executor.plan_feed_shardings(
                    prog, feed)
            return _place_feed(feed, executor, prog, shardings)

        pf = DevicePrefetcher(iter(feed_reader()), place,
                              buffer_size=buffer_size,
                              transfer_threads=transfer_threads)
        try:
            for item in pf:
                yield item
        finally:
            pf.close()

    return prefetching


def decorate_device_feed(reader, feeder, executor, program=None,
                         buffer_size=2, transfer_threads=1):
    """Raw sample-batch ``reader`` + ``DataFeeder`` -> creator of
    generators yielding committed on-device feed dicts.  Both the numpy
    conversion (``feeder.feed``) and the host->device transfer run on the
    background thread, double-buffered by default, so the step loop's
    only remaining feed cost is the executor fast path's shape/dtype
    check."""

    def feed_dicts():
        for batch in reader():
            yield feeder.feed(batch)

    return device_feed_reader(feed_dicts, executor, program=program,
                              buffer_size=buffer_size,
                              transfer_threads=transfer_threads)
