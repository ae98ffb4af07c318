"""The standing closed loop (``drivers/serve_standing.py``: every request sent
through ``InferenceEngine.generate_async`` during SET-UP, the window opens
when each has its first token, nothing arrives in it, all slots decode, then
the requests are cancelled) for a model whose cache is LATENT pages and whose
feed-forward blocks are routed experts: the same loop, clocks and stamps, with
this family's counters (``serving.decode.moe.*``, ``.latent.tokens_read``)
and its own ``correct``: after the drain the engine's OWN executables prefill
a checked request's sequence again and decode on, and the latent leaf they
leave is read, every layer's (``model.served_state_errors``); then the
mechanisms stand-alone, the served tokens, the logits and the routed sets of
the step functions on the same schedule (``model.replay``) against the plain
reference, which computes the replayed rows over the experts the step
functions took; the rows the engine's executables left in the later layers
are held to that reference too (``model.deep_row_errors``).  Every parameter comes from the configuration's and the
mix's files; the model's builder is ``models/<config.model>.py``."""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from chipbench import traffic
from chipbench.drivers.serve import percentile
from chipbench.drivers.serve_standing import HISTOGRAMS, _checked

COUNTERS = ("serving.decode.moe.pairs", "serving.decode.moe.experts_touched",
            "serving.decode.moe.max_load", "serving.decode.latent.tokens_read",
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
        checked = _checked(reqs, served, ctx.seed, mix["checked_requests"])

        def sequence(i):
            """A checked request's tokens, and where its replay is split:
            before the middle token it was served."""
            return (np.concatenate([reqs[i][0], served[i]]),
                    len(reqs[i][0]) + len(served[i]) // 2)

        held, held_rows = model.served_state_errors(
            cfg, engine.decoder, *sequence(checked[0]), ctx.seed, params,
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
    errs = model.mechanism_errors(cfg, params, ctx.seed, reference)

    def judged(table):
        return {k: e for k, e in table.items() if k not in model.NOT_JUDGED}

    if not all(e <= model.MECHANISM_RTOL.get(k, 0.0)
               for k, e in judged(errs).items()):
        bad.append("mechanisms vs reference: %s" % errs)
    checks, fns = [], model.replay_fns(cfg)
    for i in checked:
        prompt, out = reqs[i][0], served[i]
        if len(out) < 3:
            bad.append("request %d served %d tokens: nothing to check"
                       % (i, len(out)))
            continue
        P = len(prompt)
        seq, split = sequence(i)
        logits, sets, first, end = model.replay(cfg, params, seq, split,
                                                ctx.seed, fns)
        # the reference computes rows first .. end - 1 (the last whole chunk,
        # the narrow one, the decoded tokens) over the experts the step
        # functions took; the chunk before them it routes by itself
        rows = list(range(first, end))
        lo = max(0, first - cfg["chunk"])
        at = np.linspace(0, len(out) - 1, model.CHECKED_TOKENS).astype(int)
        n_logits = len(logits)
        positions = (list(range(end - n_logits, end))
                     + [P - 1 + j for j in at] + list(range(lo, end)))
        ref_logits, ref_chosen, ref_rows = model.reference_logits(
            cfg, params, seq, positions, reference, forced=(rows, sets))
        logit_err = [float(np.max(np.abs(a - b)) / b.std())
                     for a, b in zip(logits, ref_logits)]
        tok_gaps = np.asarray([model.gap(ref_logits[n_logits + n], out[j])
                               for n, j in enumerate(at)])
        tokens_agree = float((tok_gaps <= model.TIE_TOL).mean())
        own = n_logits + len(at) + first - lo     # the forced rows' places
        agree = model.routing_agreement(
            np.concatenate(sets), np.concatenate([r[own:] for r in ref_chosen]))
        if i == checked[0]:
            held.update(model.deep_row_errors(
                cfg, first, held_rows,
                [r[n_logits + len(at):] for r in ref_rows]))
        checks.append({"request": i, "context": P, "served": len(out),
                       "tokens_agree": tokens_agree,
                       "token_gap_max": float(tok_gaps.max()),
                       "logit_err": logit_err, "routing": agree})
        if not tokens_agree >= model.TOKENS_AGREE:
            bad.append("request %d: share of %d served tokens within %s logit "
                       "std of the f32 reference's top: %s"
                       % (i, len(at), model.TIE_TOL, tokens_agree))
        if not all(e <= model.LOGIT_TOL for e in logit_err):
            bad.append("request %d: chunk and decode logits vs the f32 "
                       "reference over the same experts, max error in logit "
                       "std: %s" % (i, logit_err))
        if not agree[0] >= model.ROUTING_AGREE:
            bad.append("request %d: routed experts vs the reference's (share "
                       "held, sets equal): %s" % (i, agree))
    if not (set(model.SERVED_STATE_TOL) <= set(held)
            and all(e <= model.SERVED_STATE_TOL.get(k, 0.0)
                    for k, e in judged(held).items())):
        bad.append("the engine's own programs on its own cache: %s" % held)
    if failed:
        bad.append("%d requests ended, failed or fell behind before the "
                   "window's end" % failed)
    if pages_left:
        bad.append("%d pages in use after the cancel and drain" % pages_left)
    if compiles:
        bad.append("%d compile events inside the window" % compiles)
    ctx.log("standing: served state %s; mechanism errors %s; checks %s"
            % (held, errs, checks))
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
            "served_state": held,
        },
    }
