"""What the dispatch bench's prefetch leg held with a stopwatch (async >= 1.3x
sync on the CPU), held with counts: the device-feed pipeline runs AHEAD of the
step loop.  While step k runs, the reader has been asked for batch k + 1 and
that batch is on the device before ``next()`` is called for it; each batch is
transferred once; the sequential loop asks the reader for nothing until the
step is over; and both train the same parameters, bit for bit.  How fast that
makes a step is the chip's to say (``chipbench/``)."""
import time

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu.reader import device_prefetch

from test_device_prefetch import BATCH, WIDTH, build_model

STEPS = 8


def _train(async_feed):
    """Train STEPS steps; returns (params, batches the reader had been asked
    for by the end of each step, feeds already on the device when ``next()``
    was called for them)."""
    np.random.seed(5)
    main, startup, loss = build_model()
    main.random_seed = 1234
    exe = fluid.Executor()
    feeder = fluid.DataFeeder(feed_list=["x", "y"], place=exe.place,
                              program=main)
    rng = np.random.RandomState(0)
    batches = [[(rng.randn(WIDTH).astype(np.float32),
                 rng.randn(1).astype(np.float32)) for _ in range(BATCH)]
               for _ in range(STEPS)]
    asked = [0]

    def reader():
        for b in batches:
            asked[0] += 1
            yield b

    def wait_for(reached):
        # a deadline that bounds a wait: the producer thread gets there
        # when the machine lets it, and never if nothing runs ahead
        deadline = time.perf_counter() + 30
        while not reached() and time.perf_counter() < deadline:
            time.sleep(0.001)
        return reached()

    asked_by_step, ready_at_next = [], 0
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        t0 = device_prefetch.transfer_count()
        if async_feed:
            feeds = device_prefetch.decorate_device_feed(
                reader, feeder, exe, main, buffer_size=2)()
        else:
            feeds = (feeder.feed(b) for b in reader())
        try:
            for k, feed in enumerate(feeds):
                exe.run(main, feed=feed, fetch_list=[loss])
                if async_feed and k + 1 < STEPS:
                    # still "inside" step k: batch k + 1 (x and y) lands
                    # on the device before the loop asks for it
                    ready_at_next += wait_for(
                        lambda: device_prefetch.transfer_count() - t0
                        >= 2 * (k + 2))
                asked_by_step.append(asked[0])
        finally:
            close = getattr(feeds, "close", None)
            if close is not None:
                close()
        transfers = device_prefetch.transfer_count() - t0
        params = {n: np.asarray(scope[n]).copy()
                  for n in sorted(main.persistable_names()) if n in scope}
    return params, asked_by_step, ready_at_next, transfers


def test_dispatch_bench_smoke():
    waits = obs.histogram("prefetch.wait")
    w0 = waits.count
    sync, asked_sync, _, transfers_sync = _train(async_feed=False)
    assert waits.count == w0 and transfers_sync == 0
    # the sequential loop: batch k + 1 is asked for after step k is over
    assert asked_sync == list(range(1, STEPS + 1))
    ahead, asked_async, ready, transfers = _train(async_feed=True)
    # every step but the last found its successor's feed on the device
    assert ready == STEPS - 1
    assert all(a >= k + 2 for k, a in enumerate(asked_async[:-1]))
    # one device_put a feed variable a batch, one wait a ``next()`` (the
    # STEPS feeds and the end of the stream)
    assert transfers == 2 * STEPS
    assert waits.count - w0 == STEPS + 1
    assert sync.keys() == ahead.keys()
    for name in sync:
        assert sync[name].tobytes() == ahead[name].tobytes(), name
