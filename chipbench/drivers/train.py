"""Training cells: steps through ``Executor.run`` with the device prefetcher
feeding, the way ``Trainer.train`` feeds.  Every parameter comes from the
configuration's and the traffic mix's files; the model's builder is
``models/<config.model>.py``."""
from __future__ import annotations

import time

import numpy as np


def run(ctx):
    import paddle_tpu as fluid
    from paddle_tpu.reader import device_prefetch

    cfg, mix = ctx.config, ctx.traffic
    model = ctx.registry.module("models", cfg["model"])
    np.random.seed(ctx.seed % (2 ** 32))
    main, startup, loss = model.build(cfg, mix)
    main.random_seed = startup.random_seed = ctx.seed % (2 ** 31) + 1
    feeds = model.batches(cfg, mix, ctx.seed)
    exe = fluid.Executor(ctx.place)
    feeder = fluid.DataFeeder(
        feed_list=[main.global_block().var(n) for n in feeds[0]],
        place=ctx.place, program=main)

    def reader():
        while True:
            for f in feeds:
                yield list(zip(*(f[n] for n in f)))

    losses, pending = [], []
    spans = ctx.spans

    def sync():
        with spans.span("readback"):
            losses.extend(float(np.ravel(np.asarray(x))[0]) for x in pending)
        del pending[:]

    def step(it):
        with spans.span("feed"):
            feed = next(it)
        with spans.span("dispatch"):
            out = exe.run(main, feed=feed, fetch_list=[loss])
        pending.append(out[0])

    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        it = device_prefetch.decorate_device_feed(
            reader, feeder, exe, main, buffer_size=mix["buffer_size"])()
        try:
            for _ in range(mix["warmup_steps"]):
                step(it)
            sync()
            spans.seconds.clear()
            # ---- the measured window -------------------------------------
            compiles0 = ctx.compiles()
            setup_s = ctx.since_start()
            t0 = time.perf_counter()
            steps, trace, trace_from = 0, None, None
            while True:
                if ctx.trace and trace_from is None and \
                        time.perf_counter() - t0 >= mix["trace_after_s"]:
                    sync()
                    ctx.tracer.start()
                    trace_from = steps
                step(it)
                steps += 1
                if trace_from is not None and trace is None and \
                        steps - trace_from == mix["trace_steps"]:
                    sync()
                    trace = ctx.tracer.stop()
                if steps % mix["sync_every"] == 0:
                    sync()
                    if time.perf_counter() - t0 >= ctx.seconds and \
                            (trace_from is None or trace is not None):
                        break
            sync()
            window_s = time.perf_counter() - t0
            compiles = ctx.compiles() - compiles0
        finally:
            it.close()

    items = model.items_per_step(cfg, mix)
    flops = model.flops_per_step(cfg, mix)
    ctx.log("train: %d steps in %.3f s; %.1f %s/s; %.2f model TFLOP/step, "
            "%.2f model TFLOP/s end to end; loss %.4f -> %.4f"
            % (steps, window_s, steps * items / window_s, cfg["item"],
               flops / 1e12, flops * steps / window_s / 1e12,
               losses[0], losses[-1]))
    bad = model.check(cfg, mix, ctx.seed, losses,
                      ctx.registry.reference(cfg["name"]))
    if compiles:
        bad.append("%d compile events inside the window" % compiles)
    for b in bad:
        ctx.log("train: NOT CORRECT: " + b)
    window_losses = losses[mix["warmup_steps"]:]
    return {
        "correct": not bad,
        "attempted": steps,
        "failed": int(sum(not np.isfinite(x) for x in window_losses)),
        "end_to_end": {"train_items_per_s": steps * items / window_s,
                       "setup_s": setup_s},
        "observed": {
            "spans": spans.seconds, "trace": trace, "steps": steps,
            "traced_steps": mix["trace_steps"] if trace else 0,
            "window_s": window_s,
            "compiles_in_window": compiles, "flops_per_step": flops,
        },
    }
