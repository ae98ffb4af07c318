"""Serving cells with STANDING requests: a closed loop.  Every request of the
mix (``requests`` of them, as many as the configuration has slots) is sent
through the front door (``InferenceEngine.generate_async``) during SET-UP;
set-up ends, and the measured window starts, when every one has its first
token.  Nothing arrives inside the window: all slots decode through it, then
the driver cancels the requests (``GenerateRequest.cancel()``).  So
``setup_s`` carries the chunked prefill of every context and is the guard on
that path, and the window is pure decode over full slots.

``serve_tokens_per_s`` = tokens stamped inside the window / its length;
``itl_p95_ms`` = 95th percentile of the gaps between a request's consecutive
stamps inside the window, pooled.  A request that ended, failed or fell a
token behind before the window's end is ``failed``.  ``correct`` holds the
cache's guarantees on the object that was timed (after the drain the
engine's own step programs run once more into its own cache:
``model.served_state_errors``), then the mechanisms, the served tokens, the
step functions' logits and the selected sets against the plain reference.
Every parameter comes
from the configuration's and the mix's files; the model's builder is
``models/<config.model>.py``.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from chipbench import traffic
from chipbench.drivers.serve import percentile

HISTOGRAMS = ("serving.decode.queue_wait", "serving.decode.step",
              "serving.decode.prefill")
COUNTERS = ("serving.decode.sparse.selected_tokens",
            "serving.decode.sparse.visible_tokens",
            "serving.decode.sparse.dense_rows",
            "serving.decode.prefill_tokens", "serving.decode.steps")


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
        ctx.log("standing: %d requests, %d prompt tokens prefilled in %.1f s "
                "(%.1f s inside the chunk program: %.0f tokens/s)"
                % (len(reqs), prompt_tokens, time.perf_counter() - t_send,
                   prefill.sum, prompt_tokens / max(prefill.sum, 1e-9)))
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
        checked = _checked(reqs, served, ctx.seed, mix["checked_requests"])
        held = model.served_state_errors(
            cfg, engine.decoder,
            np.concatenate([reqs[checked[0]][0], served[checked[0]]]),
            ctx.seed)
    finally:
        engine.stop()
    del engine
    tokens_per_s = sum(in_window) / ctx.seconds
    itl_p95 = 1e3 * percentile(gaps, 95) if gaps else float("nan")
    ctx.log("standing: %d of %d requests decoded through the %.0f s window "
            "(%d tokens each at most); %.1f tokens/s; itl p50 %.2f p95 %.2f "
            "ms; at the window's end %d active, %d KV pages in use (%.1f%% "
            "of the pool); %d pages in use after the cancel"
            % (len(futures) - failed, len(futures), ctx.seconds, most,
               tokens_per_s, 1e3 * percentile(gaps, 50) if gaps else 0.0,
               itl_p95, health_end["active"], health_end["kv_pages_used"],
               100.0 * health_end["kv_occupancy"], pages_left))

    # ---- correct, against the reference: the engine's pools given back
    errs = model.mechanism_errors(cfg, ctx.seed, reference)
    if not all(e <= model.MECHANISM_RTOL.get(k, 0.0) for k, e in errs.items()):
        bad.append("mechanisms vs reference: %s" % errs)
    if not all(e <= model.SERVED_STATE_TOL.get(k, 0.0)
               for k, e in held.items()):
        bad.append("the engine's own programs on its own cache: %s" % held)
    checks = []
    for i in checked:
        prompt, out = reqs[i][0], served[i]
        if len(out) < 3:
            bad.append("request %d served %d tokens: nothing to check"
                       % (i, len(out)))
            continue
        P, mid, last = len(prompt), len(out) // 2, len(out) - 1
        seq = np.concatenate([prompt, out])
        split = P + mid                  # replay prefill ends before out[mid]
        positions = [P - 1, split - 1, split, P + last - 1]
        ref_logits, ref_sel = model.reference_logits(
            cfg, params, seq, positions, reference)
        tok_gaps = [model.gap(ref_logits[j], out[k])
                    for j, k in ((0, 0), (1, mid), (3, last))]
        c_logits, d_logits, c_sel, d_sel = model.replay(cfg, params, seq, split)
        logit_err = [float(np.max(np.abs(a - b)) / b.std()) for a, b in
                     ((c_logits, ref_logits[1]), (d_logits, ref_logits[2]))]
        agree = [model.selection_agreement(s, r[j]) for j, sel in
                 ((1, c_sel), (2, d_sel)) for s, r in zip(sel, ref_sel)]
        checks.append({"request": i, "context": P, "served": len(out),
                       "token_gaps": tok_gaps, "logit_err": logit_err,
                       "selection": agree})
        if not all(g <= model.TIE_TOL for g in tok_gaps):
            bad.append("request %d: first, middle, last served tokens vs the "
                       "f32 reference, gaps in logit std: %s" % (i, tok_gaps))
        if not all(e <= model.LOGIT_TOL for e in logit_err):
            bad.append("request %d: chunk and decode logits vs the f32 "
                       "reference, max error in logit std: %s" % (i, logit_err))
        if not all(a >= model.SELECTION_AGREE for a, _ in agree):
            bad.append("request %d: selected blocks vs the reference's "
                       "(share held, extra): %s" % (i, agree))
    if failed:
        bad.append("%d requests ended, failed or fell behind before the "
                   "window's end" % failed)
    if pages_left:
        bad.append("%d KV pages in use after the cancel and drain" % pages_left)
    if compiles:
        bad.append("%d compile events inside the window" % compiles)
    ctx.log("standing: served state %s; mechanism errors %s; checks %s"
            % (held, errs, checks))
    for b in bad:
        ctx.log("standing: NOT CORRECT: " + b)
    return {
        "correct": not bad, "attempted": len(futures), "failed": failed,
        "end_to_end": {"serve_tokens_per_s": tokens_per_s,
                       "itl_p95_ms": itl_p95, "setup_s": setup_s},
        "observed": {
            "attempted": len(futures), "completed": len(futures) - failed,
            "seconds": ctx.seconds, "histograms": hist,
            "setup": {"prompt_tokens": prompt_tokens,
                      "prefill_s": prefill.sum,
                      "counters": {c: count1[c] - count0[c] for c in COUNTERS}},
            "window_counters": {c: count2[c] - count1[c] for c in COUNTERS},
            "active_slots": health_end["active"],
            "kv_pages_used_at_end": health_end["kv_pages_used"],
            "trace": trace.get("trace"), "traced_steps": trace.get("steps"),
            "compiles_in_window": compiles, "checks": checks,
            "served_state": held,
        },
    }


def _checked(reqs, served, seed, n):
    """The requests whose tokens are checked: the longest context, then
    others by the seed."""
    order = [int(np.argmax([len(p) for p, _ in reqs]))]
    rng = np.random.RandomState(seed % (2 ** 32))
    for i in rng.permutation(len(reqs)):
        if len(order) >= n:
            break
        if int(i) not in order:
            order.append(int(i))
    return order[:n]
