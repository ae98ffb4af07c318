"""Async device-feed pipeline (reader.device_prefetch) semantics:

- prefetch-on training is BITWISE-equal to prefetch-off (single device
  and under a mesh) — the pipeline moves work off the critical path, it
  never changes values;
- committed on-device feeds dispatch with ZERO host-side feed copies
  (executor.feed_host_copy_count) and each batch transfers exactly once
  (device_prefetch.transfer_count);
- abandoning the pipeline (break/exception/GeneratorExit) leaves no live
  producer thread and closes the source reader;
- reader/conversion/transfer errors propagate to the consumer;
- a slow reader's cost overlaps compute (timing, generous margins);
- ParallelExecutor per-device feed lists take the sharded device-put
  path (no host concatenation) and match the merged-feed result.
"""
from __future__ import annotations

import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import executor as executor_mod
from paddle_tpu.reader import device_prefetch

WIDTH = 8
BATCH = 8


def build_model(optimizer="sgd"):
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[WIDTH], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            h = fluid.layers.fc(x, size=WIDTH, act="relu")
            pred = fluid.layers.fc(h, size=1)
            loss = fluid.layers.mean(fluid.layers.square(pred - y))
            if optimizer == "sgd":
                fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def sample_batches(n_batches, seed=0, delay=0.0):
    rng = np.random.RandomState(seed)
    batches = [
        [(rng.randn(WIDTH).astype(np.float32),
          rng.randn(1).astype(np.float32)) for _ in range(BATCH)]
        for _ in range(n_batches)
    ]

    def reader():
        for b in batches:
            if delay:
                time.sleep(delay)
            yield b

    return reader


def _train(async_feed, mesh=False, steps=6):
    np.random.seed(5)
    main, startup, loss = build_model()
    main.random_seed = 1234
    scope = fluid.Scope()
    exe = fluid.Executor()
    if mesh:
        exe.attach_mesh(True)
    feeder = fluid.DataFeeder(feed_list=["x", "y"], place=exe.place,
                              program=main)
    reader = sample_batches(steps)
    with fluid.scope_guard(scope):
        exe.run(startup)
        if async_feed:
            feeds = device_prefetch.decorate_device_feed(
                reader, feeder, exe, main, buffer_size=2)()
        else:
            feeds = (feeder.feed(b) for b in reader())
        try:
            for feed in feeds:
                out = exe.run(main, feed=feed, fetch_list=[loss])
        finally:
            close = getattr(feeds, "close", None)
            if close is not None:
                close()
        assert np.isfinite(float(np.ravel(np.asarray(out[0]))[0]))
        params = {
            n: np.asarray(scope[n]).copy()
            for n in sorted(main.persistable_names()) if n in scope
        }
    return params


@pytest.mark.parametrize("mesh", [False, True])
def test_async_training_bitwise_equals_sync(mesh):
    sync = _train(False, mesh=mesh)
    async_ = _train(True, mesh=mesh)
    assert sync.keys() == async_.keys()
    for n in sync:
        assert sync[n].tobytes() == async_[n].tobytes(), (
            "prefetch changed parameter %r" % n)


def test_on_device_feeds_zero_host_copies():
    """The acceptance contract: Executor.run with committed device arrays
    performs no host-side copies of feed data, and the fast path stays
    engaged."""
    np.random.seed(5)
    main, startup, loss = build_model()
    scope = fluid.Scope()
    exe = fluid.Executor()
    feeder = fluid.DataFeeder(feed_list=["x", "y"], place=exe.place,
                              program=main)
    batch = next(iter(sample_batches(1)()))
    with fluid.scope_guard(scope):
        exe.run(startup)
        dev_feed = device_prefetch.put_feed_on_device(
            feeder.feed(batch), exe, main)
        for v in dev_feed.values():  # really on device, committed
            assert executor_mod.Executor._is_device_array(v)
        for _ in range(3):  # engage + bind the fast path
            exe.run(main, feed=dev_feed, fetch_list=[loss])
        assert exe._bound, "fast path never bound with device feeds"
        before = executor_mod.feed_host_copy_count()
        t_before = device_prefetch.transfer_count()
        for _ in range(5):
            out = exe.run(main, feed=dev_feed, fetch_list=[loss])
        np.asarray(out[0])
        assert executor_mod.feed_host_copy_count() == before, (
            "on-device feeds paid host-side conversions")
        assert device_prefetch.transfer_count() == t_before, (
            "steady-state dispatch re-transferred already-committed feeds")
        # control: host feeds DO count host conversions (the instrument
        # itself works)
        exe.fast_path = False
        exe.run(main, feed=feeder.feed(batch), fetch_list=[loss])
        assert executor_mod.feed_host_copy_count() > before


def test_prefetcher_transfers_each_batch_once():
    np.random.seed(5)
    main, startup, loss = build_model()
    scope = fluid.Scope()
    exe = fluid.Executor()
    feeder = fluid.DataFeeder(feed_list=["x", "y"], place=exe.place,
                              program=main)
    with fluid.scope_guard(scope):
        exe.run(startup)
        before = device_prefetch.transfer_count()
        feeds = device_prefetch.decorate_device_feed(
            sample_batches(4), feeder, exe, main)()
        for feed in feeds:
            exe.run(main, feed=feed, fetch_list=[loss])
    # 4 batches x 2 feed vars, one device_put each
    assert device_prefetch.transfer_count() - before == 8


def _pipeline_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith(("paddle-tpu-device-prefetch",
                                  "paddle-tpu-buffered-pump",
                                  "paddle-tpu-interleave-pump"))]


def _assert_no_pipeline_threads(timeout=5.0):
    deadline = time.monotonic() + timeout
    while _pipeline_threads() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not _pipeline_threads(), (
        "producer threads leaked: %r" % _pipeline_threads())


def test_prefetcher_abandoned_early_leaves_no_threads_and_closes_reader():
    np.random.seed(5)
    main, startup, loss = build_model()
    scope = fluid.Scope()
    exe = fluid.Executor()
    feeder = fluid.DataFeeder(feed_list=["x", "y"], place=exe.place,
                              program=main)
    closed = []
    batches = sample_batches(1000, delay=0.001)

    def reader():
        try:
            yield from batches()
        finally:
            closed.append(True)

    with fluid.scope_guard(scope):
        exe.run(startup)
        feeds = device_prefetch.decorate_device_feed(
            reader, feeder, exe, main, buffer_size=2)()
        first = next(feeds)
        exe.run(main, feed=first, fetch_list=[loss])
        feeds.close()  # consumer walks away mid-stream
    _assert_no_pipeline_threads()
    assert closed, "underlying reader was not closed on abandonment"


def test_prefetcher_break_out_of_for_loop_leaves_no_threads():
    np.random.seed(5)
    main, startup, loss = build_model()
    scope = fluid.Scope()
    exe = fluid.Executor()
    feeder = fluid.DataFeeder(feed_list=["x", "y"], place=exe.place,
                              program=main)
    with fluid.scope_guard(scope):
        exe.run(startup)
        feeds = device_prefetch.decorate_device_feed(
            sample_batches(500, delay=0.001), feeder, exe, main)()
        try:
            for i, feed in enumerate(feeds):
                exe.run(main, feed=feed, fetch_list=[loss])
                if i == 1:
                    break
        finally:
            feeds.close()
    _assert_no_pipeline_threads()


def test_prefetcher_dropped_without_close_is_finalized():
    """A raw DevicePrefetcher abandoned WITHOUT close() must still tear
    down via its GC finalizer — the worker threads deliberately hold no
    reference to the instance, so dropping the last ref reclaims it."""
    import gc

    def endless():
        i = 0
        while True:
            yield {"x": np.zeros((2, WIDTH), np.float32)}
            i += 1

    pf = device_prefetch.DevicePrefetcher(endless(), buffer_size=2)
    next(pf)
    del pf
    gc.collect()
    _assert_no_pipeline_threads()


def test_prefetcher_propagates_reader_error():
    np.random.seed(5)
    main, startup, _loss = build_model()
    scope = fluid.Scope()
    exe = fluid.Executor()
    feeder = fluid.DataFeeder(feed_list=["x", "y"], place=exe.place,
                              program=main)
    good = sample_batches(2)

    def broken():
        yield from good()
        raise IOError("corrupt shard mid-stream")

    with fluid.scope_guard(scope):
        exe.run(startup)
        feeds = device_prefetch.decorate_device_feed(
            broken, feeder, exe, main)()
        got = []
        with pytest.raises(IOError, match="corrupt shard"):
            for feed in feeds:
                got.append(feed)
    assert len(got) == 2, "samples before the failure must be delivered"
    _assert_no_pipeline_threads()


def test_prefetcher_propagates_conversion_error():
    np.random.seed(5)
    main, startup, _loss = build_model()
    exe = fluid.Executor()
    feeder = fluid.DataFeeder(feed_list=["x", "y"], place=exe.place,
                              program=main)

    def bad_batches():
        yield [(np.zeros(WIDTH, np.float32),)] * BATCH  # missing a slot

    feeds = device_prefetch.decorate_device_feed(
        bad_batches, feeder, exe, main)()
    with pytest.raises(AssertionError, match="slots"):
        list(feeds)
    _assert_no_pipeline_threads()


def test_slow_reader_overlaps_compute():
    """A reader sleeping 20ms/batch against a step loop costing ~15ms
    (exe.run on a tiny model + a sleep standing in for device compute —
    wall-clock stable on a loaded CI host; the smoke-gated dispatch bench
    covers real-compute overlap).  Serially that is ~35ms/step; with the
    prefetcher the reader's cost must hide behind the steps."""
    np.random.seed(5)
    main, startup, loss = build_model()
    scope = fluid.Scope()
    exe = fluid.Executor()
    feeder = fluid.DataFeeder(feed_list=["x", "y"], place=exe.place,
                              program=main)
    n, delay, work = 10, 0.02, 0.015
    with fluid.scope_guard(scope):
        exe.run(startup)
        warm = feeder.feed(next(iter(sample_batches(1)())))
        for feed in (warm, device_prefetch.put_feed_on_device(warm, exe, main)):
            for _ in range(3):
                np.asarray(exe.run(main, feed=feed, fetch_list=[loss])[0])

        def leg(async_feed):
            reader = sample_batches(n, delay=delay)
            t0 = time.perf_counter()
            if async_feed:
                feeds = device_prefetch.decorate_device_feed(
                    reader, feeder, exe, main, buffer_size=2)()
            else:
                feeds = (feeder.feed(b) for b in reader())
            try:
                for feed in feeds:
                    np.asarray(exe.run(main, feed=feed,
                                       fetch_list=[loss])[0])
                    time.sleep(work)
            finally:
                close = getattr(feeds, "close", None)
                if close is not None:
                    close()
            return time.perf_counter() - t0

        t_sync = leg(False)
        t_async = leg(True)
    # sync pays reader + step serially (~0.35s); async hides the reader
    # behind the steps (~0.22s).  The 20% bound leaves ~80ms of noise
    # headroom on a 130ms structural difference.
    assert t_async < 0.8 * t_sync, (
        "no overlap: sync %.3fs async %.3fs (reader floor %.3fs)"
        % (t_sync, t_async, n * delay))


def test_trainer_routes_reader_through_prefetch_bitwise():
    def run(prefetch):
        np.random.seed(17)

        def train_func():
            x = fluid.layers.data(name="x", shape=[WIDTH], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            h = fluid.layers.fc(x, size=WIDTH, act="relu")
            pred = fluid.layers.fc(h, size=1)
            return fluid.layers.mean(fluid.layers.square(pred - y))

        trainer = fluid.Trainer(
            train_func=train_func,
            optimizer_func=lambda: fluid.optimizer.SGD(learning_rate=0.1))
        trainer.train_program.random_seed = 77
        trainer.train(num_epochs=1, reader=sample_batches(5),
                      feed_order=["x", "y"], prefetch=prefetch)
        with fluid.scope_guard(trainer.scope):
            return {
                n: np.asarray(trainer.scope[n]).copy()
                for n in sorted(trainer.train_program.persistable_names())
                if n in trainer.scope
            }

    off = run(False)
    on = run(True)
    assert off.keys() == on.keys()
    for n in off:
        assert off[n].tobytes() == on[n].tobytes(), (
            "Trainer prefetch changed parameter %r" % n)
    _assert_no_pipeline_threads()


def test_parallel_executor_feed_list_takes_sharded_path():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[WIDTH], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            h = fluid.layers.fc(x, size=WIDTH, act="relu")
            pred = fluid.layers.fc(h, size=1)
            loss = fluid.layers.mean(fluid.layers.square(pred - y))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor().run(startup)
        pexe = fluid.ParallelExecutor(main_program=main, loss_name=loss.name,
                                      scope=scope)
        n = pexe.device_count
        rng = np.random.RandomState(3)
        X = rng.randn(2 * n, WIDTH).astype(np.float32)
        Y = rng.randn(2 * n, 1).astype(np.float32)
        whole = float(np.ravel(
            pexe.run(fetch_list=[loss], feed={"x": X, "y": Y})[0])[0])
        before = device_prefetch.transfer_count()
        parts = [{"x": X[2 * i:2 * i + 2], "y": Y[2 * i:2 * i + 2]}
                 for i in range(n)]
        split = float(np.ravel(
            pexe.run(fetch_list=[loss], feed=parts)[0])[0])
        # per-shard device_put, one per (var, device) — NOT a host concat
        assert device_prefetch.transfer_count() - before == 2 * n
        assert abs(whole - split) < 1e-6

        # single-entry list short-circuits without any copy at all
        before = device_prefetch.transfer_count()
        one = float(np.ravel(
            pexe.run(fetch_list=[loss], feed=[{"x": X, "y": Y}])[0])[0])
        assert device_prefetch.transfer_count() == before
        assert abs(whole - one) < 1e-6


def test_put_feed_on_device_respects_mesh_sharding():
    main, startup, _loss = build_model()
    exe = fluid.Executor()
    mesh = exe.attach_mesh(True)
    feed = {"x": np.zeros((BATCH, WIDTH), np.float32),
            "y": np.zeros((BATCH, 1), np.float32)}
    dev = device_prefetch.put_feed_on_device(feed, exe, main)
    from jax.sharding import NamedSharding, PartitionSpec as P

    for name in ("x", "y"):
        assert dev[name].sharding == NamedSharding(mesh, P("dp")), name
    # non-divisible batch stays replicated instead of erroring
    odd = {"x": np.zeros((3, WIDTH), np.float32)}
    dev_odd = device_prefetch.put_feed_on_device(odd, exe, main)
    assert dev_odd["x"].sharding == NamedSharding(mesh, P())


def test_prefetcher_casts_to_declared_dtype_off_critical_path():
    main, startup, _loss = build_model()
    exe = fluid.Executor()
    feed = {"x": np.zeros((BATCH, WIDTH), np.float64),
            "y": np.zeros((BATCH, 1), np.float64)}
    dev = device_prefetch.put_feed_on_device(feed, exe, main)
    assert str(dev["x"].dtype) == "float32"
    assert str(dev["y"].dtype) == "float32"


def test_host_and_prefetched_feeds_share_one_executable():
    """jax keys executables on whether each argument is committed: a step
    fed from the host and then from the prefetcher (and state fresh from
    startup vs already stepped) used to compile up to four times underneath
    ONE executor entry.  The step's placement is explicit now, so int64
    labels from numpy, int32 device arrays from the prefetcher and either
    kind of state are the same executable."""
    import jax.monitoring

    backend_compiles = []

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            backend_compiles.append(duration)

    main = fluid.Program()
    startup = fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[WIDTH], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        logits = fluid.layers.fc(x, size=4)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    rng = np.random.RandomState(0)
    rows = [(rng.randn(WIDTH).astype(np.float32),
             rng.randint(0, 4, size=1).astype(np.int64)) for _ in range(BATCH)]
    host_feed = {"x": np.stack([r[0] for r in rows]),
                 "y": np.stack([r[1] for r in rows])}
    exe = fluid.Executor()
    feeder = fluid.DataFeeder(feed_list=["x", "y"], place=exe.place,
                              program=main)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed=host_feed, fetch_list=[loss])
        entries = executor_mod.compile_count()
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        try:
            exe.run(main, feed=host_feed, fetch_list=[loss])
            for feed in device_prefetch.decorate_device_feed(
                    lambda: iter([rows, rows, rows]), feeder, exe, main,
                    buffer_size=2)():
                assert str(feed["y"].dtype) == "int32"  # x64 is off
                exe.run(main, feed=feed, fetch_list=[loss])
            np.asarray(exe.run(main, feed=host_feed, fetch_list=[loss])[0])
        finally:
            jax.monitoring.unregister_event_duration_listener(on_duration)
    assert executor_mod.compile_count() == entries
    assert backend_compiles == []
