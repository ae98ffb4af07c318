"""The standing closed loop (``drivers/serve_standing.py``: every request sent
through ``InferenceEngine.generate_async`` during SET-UP, the window opens
when each has its first token, nothing arrives in it, all slots decode, then
the requests are cancelled) for a model whose cache is LATENT pages beside an
indexer's key pages, whose attention reads a learned selection of rows and
whose feed-forward blocks are a share of routed experts: the same loop, clocks
and stamps, with this family's counters (``serving.decode.sparse.*``,
``.index.rows_scored``, ``.moe.*``) and its own ``correct``.  The expert side
and the cache's rows are ``chipbench/moe_check.py``'s two calls (the engine's
OWN executables once more into its own cache, then the mechanisms, the served
tokens, the step functions' logits and routed sets against the plain reference
over the same experts and the same selected sets); the selection itself
(index scores, the share of the reference's set held, every flip's distance
from the threshold, the logits against the reference on its own sets) is
``model.selection_checks``.  After the window the compiled decode program's
text is read once (``DecodeScheduler.decode_program_text``) for the per-layer
readers of the three stages (``chipbench/glm5_decode.py``).  Every parameter
comes from the configuration's and the mix's files; the model's builder is
``models/<config.model>.py``."""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from chipbench import glm5_decode, moe_check, traffic
from chipbench.drivers.serve import percentile
from chipbench.drivers.serve_standing import HISTOGRAMS

COUNTERS = ("serving.decode.moe.pairs", "serving.decode.moe.experts_touched",
            "serving.decode.moe.max_load", "serving.decode.moe.pairs_elsewhere",
            "serving.decode.latent.tokens_read",
            "serving.decode.sparse.selected_tokens",
            "serving.decode.sparse.visible_tokens",
            "serving.decode.index.rows_scored",
            "serving.decode.prefill_tokens", "serving.decode.steps")
# the reference pass of a checked sequence runs on it padded to a multiple,
# no shorter than the least: one compiled reference for lengths near each other
REFERENCE_PAD = (2048, 8192)


def _checked(reqs, n, least):
    """The requests whose tokens are checked: the shortest context of at
    least ``least`` tokens, then the shortest of the others (the reference's
    pass over a sequence holds ``[T, T]`` scores and every head's expanded
    keys beside the weights: 2 GB at 14 k tokens, not at 50 k)."""
    by_len = sorted(range(len(reqs)), key=lambda i: (len(reqs[i][0]), i))
    long = [i for i in by_len if len(reqs[i][0]) >= least]
    order = long[:1] + [i for i in by_len if i not in long[:1]]
    return order[:n]


def _counters():
    from paddle_tpu import observability as obs

    return {c: obs.counter(c).value for c in COUNTERS}


def run(ctx):
    from paddle_tpu import observability as obs

    cfg, mix = ctx.config, ctx.traffic
    model = ctx.registry.module("models", cfg["model"])
    reference = ctx.registry.reference(cfg["name"])
    params, meta = model.make_params(cfg, ctx.seed)
    t = time.perf_counter()
    engine = model.build_engine(cfg, params, meta, mix["output_len"]["max"])
    ctx.log("standing: engine warmed up in %.1f s" % (time.perf_counter() - t))
    reqs = traffic.requests(mix, mix["requests"], ctx.seed, cfg["vocab_size"])
    trace = {}
    bad = []
    try:
        # ---- set-up: every request in, every one to its first token
        hist0 = {h: obs.histogram(h).snapshot() for h in HISTOGRAMS}
        count0 = _counters()
        t_send = time.perf_counter()
        futures = [engine.generate_async(p, max_new_tokens=n) for p, n in reqs]
        limit = t_send + mix["setup_limit_s"]
        while (any(not f.token_times and not f.done() for f in futures)
               and time.perf_counter() < limit):
            time.sleep(0.05)
        prefill = obs.histogram("serving.decode.prefill").snapshot() - hist0[
            "serving.decode.prefill"]
        prompt_tokens = int(sum(len(p) for p, _ in reqs))
        prefill_wall_s = time.perf_counter() - t_send
        ctx.log("standing: %d requests, %d prompt tokens prefilled in %.1f s "
                "(%.1f s inside the chunk program: %.0f tokens/s)"
                % (len(reqs), prompt_tokens, prefill_wall_s, prefill.sum,
                   prompt_tokens / max(prefill.sum, 1e-9)))
        compiles0 = ctx.compiles()
        hist1 = {h: obs.histogram(h).snapshot() for h in HISTOGRAMS}
        count1 = _counters()
        setup_s = ctx.since_start()

        # ---- the window: nothing arrives, every slot decodes
        t0 = time.perf_counter()
        tracer = None
        if ctx.trace:
            def body():
                time.sleep(mix["trace_after_share"] * ctx.seconds)
                ctx.tracer.start()
                steps0 = obs.histogram("serving.decode.step").snapshot()
                time.sleep(mix["trace_s"])
                trace["steps"] = (obs.histogram("serving.decode.step")
                                  .snapshot() - steps0).count
                trace["trace"] = ctx.tracer.stop()
            tracer = threading.Thread(target=body, name="chipbench-tracer")
            tracer.start()
        time.sleep(max(0.0, t0 + ctx.seconds - time.perf_counter()))
        t1 = time.perf_counter()
        ended_early = [f.done() for f in futures]
        health_end = engine.health()["decode"]
        hist = {h: obs.histogram(h).snapshot() - hist1[h] for h in HISTOGRAMS}
        count2 = _counters()
        compiles = ctx.compiles() - compiles0
        if tracer is not None:
            tracer.join()

        # ---- cancel, drain, and read the client's stamps
        for f in futures:
            f.cancel()
        drain_end = time.perf_counter() + mix["drain_limit_s"]
        while (not all(f.done() for f in futures)
               and time.perf_counter() < drain_end):
            time.sleep(0.02)
        while (engine.health()["decode"]["kv_pages_used"]
               and time.perf_counter() < drain_end):
            time.sleep(0.02)
        pages_left = engine.health()["decode"]["kv_pages_used"]
        in_window, gaps, served = [], [], []
        for f in futures:
            stamps = np.asarray(f.token_times, np.float64)
            inside = stamps[(stamps > t0) & (stamps <= t1)]
            in_window.append(len(inside))
            gaps.extend(np.diff(inside))
            served.append(np.asarray(f.journal.accepted, np.int32))
        most = max(in_window) if in_window else 0
        failed = sum(1 for early, n, f in zip(ended_early, in_window, futures)
                     if early or not f.token_times or n < most - 1)

        # ---- correct, on the object that was timed: the engine's own step
        # programs once more into its own cache, and what they leave there
        engine.stop()
        stages = glm5_decode.stage_names(engine.decoder.decode_program_text())
        checked = _checked(reqs, mix["checked_requests"],
                           4 * cfg["index_topk"])
        checked_reqs = [(reqs[i][0], served[i]) for i in checked]
        state = moe_check.served_state(model, cfg, engine.decoder,
                                       checked_reqs[0], ctx.seed, params,
                                       reference)
    finally:
        engine.stop()
    # the engine is a cycle (scheduler <-> worker <-> futures): collect it
    # now, so that its pool is gone before the checks build one of their own
    # (two pools beside the weights are the whole chip)
    attempted = len(futures)
    del engine, futures, f
    gc.collect()
    tokens_per_s = sum(in_window) / ctx.seconds
    itl_p95 = 1e3 * percentile(gaps, 95) if gaps else float("nan")
    ctx.log("standing: %d of %d requests decoded through the %.0f s window "
            "(%d tokens each at most); %.1f tokens/s; itl p50 %.2f p95 %.2f "
            "ms; at the window's end %d active, %d pages in use (%.1f%% of "
            "the pool); %d pages in use after the cancel"
            % (attempted - failed, attempted, ctx.seconds, most,
               tokens_per_s, 1e3 * percentile(gaps, 50) if gaps else 0.0,
               itl_p95, health_end["active"], health_end["kv_pages_used"],
               100.0 * health_end["kv_occupancy"], pages_left))

    # ---- correct, against the reference: the engine's pool given back
    bad, checks, errs, held = moe_check.check(
        model, cfg, params, reference, ctx.seed, checked_reqs, state,
        pad=REFERENCE_PAD)
    more, selection = model.selection_checks(params, reference)
    bad += more
    if failed:
        bad.append("%d requests ended, failed or fell behind before the "
                   "window's end" % failed)
    if pages_left:
        bad.append("%d pages in use after the cancel and drain" % pages_left)
    if compiles:
        bad.append("%d compile events inside the window" % compiles)
    ctx.log("standing: served state %s; mechanism errors %s; checks %s; "
            "selection %s" % (held, errs, checks, selection))
    for b in bad:
        ctx.log("standing: NOT CORRECT: " + b)
    return {
        "correct": not bad, "attempted": attempted, "failed": failed,
        "end_to_end": {"serve_tokens_per_s": tokens_per_s,
                       "itl_p95_ms": itl_p95, "setup_s": setup_s},
        "observed": {
            "attempted": attempted, "completed": attempted - failed,
            "seconds": ctx.seconds, "histograms": hist,
            "setup": {"prompt_tokens": prompt_tokens,
                      "prefill_s": prefill.sum,
                      "prefill_wall_s": prefill_wall_s,
                      "counters": {c: count1[c] - count0[c] for c in COUNTERS}},
            "window_counters": {c: count2[c] - count1[c] for c in COUNTERS},
            "active_slots": health_end["active"],
            "kv_pages_used_at_end": health_end["kv_pages_used"],
            "trace": trace.get("trace"), "traced_steps": trace.get("steps"),
            "compiles_in_window": compiles, "checks": checks,
            "selection": selection, "served_state": held,
            "decode_stages": stages,
        },
    }
