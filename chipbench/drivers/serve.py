"""Serving cells: requests through a front door (``InferenceEngine.
generate_async``) in an open loop at the rate the traffic mix fixes.  Every
parameter comes from the configuration's and the mix's files; the model's
builder is ``models/<config.model>.py``.

Times are the client's: a request's clock starts when it was DUE, not when it
was sent, so a stall counts against the requests behind it; tokens are stamped
by ``GenerateRequest.token_times``.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from chipbench import traffic

HISTOGRAMS = ("serving.decode.queue_wait", "serving.decode.step")


def percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


def offer(engine, due, requests, t0):
    """Send each request at its due time (seconds after ``t0``); returns the
    futures (the exception where the front door refused) and how late each was sent."""
    futures, lag = [], []
    for at, (prompt, n_new) in zip(due, requests):
        wait = t0 + at - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        lag.append(time.perf_counter() - (t0 + at))
        try:
            futures.append(engine.generate_async(prompt, max_new_tokens=n_new))
        except Exception as e:  # noqa: BLE001 — a refusal is a failed request
            futures.append(e)
    return futures, lag


def window(engine, mix, vocab, seconds, seed, rate=None, during=None):
    """One measured window: offers the mix's traffic for ``seconds``, waits
    for the stragglers (at most ``drain_limit_s``), and reduces the client's
    stamps.  ``during(t0)`` may start something beside it (the tracer)."""
    from paddle_tpu import observability as obs

    due = traffic.arrivals(mix, seconds, seed, rate)
    reqs = traffic.requests(mix, len(due), seed, vocab)
    hist0 = {h: obs.histogram(h).snapshot() for h in HISTOGRAMS}
    prefix0 = engine.health()["decode"].get("prefix", {})
    t0 = time.perf_counter()
    side = during(t0) if during else None
    futures, lag = offer(engine, due, reqs, t0)
    end = t0 + seconds
    time.sleep(max(0.0, end - time.perf_counter()))
    health_end = engine.health()["decode"]
    hist = {h: obs.histogram(h).snapshot() - hist0[h] for h in HISTOGRAMS}
    limit = end + mix["drain_limit_s"]
    ttft, gaps, done, failed, tokens_in_window, samples = [], [], 0, 0, 0, []
    for at, (prompt, n_new), fut in zip(due, reqs, futures):
        out = None
        if not isinstance(fut, Exception):
            try:
                out = fut.result(timeout=max(0.0, limit - time.perf_counter()))
            except Exception:  # noqa: BLE001 — failed, shed or still running
                out = None
        if out is None or len(out) != n_new:
            failed += 1
            ttft.append(seconds)     # a failure counts as the window's length
            continue
        done += 1
        stamps = np.asarray(fut.token_times) - t0
        ttft.append(stamps[0] - at)
        gaps.extend(np.diff(stamps))
        tokens_in_window += int(np.sum(stamps <= seconds))
        if len(samples) < mix["checked_requests"]:
            samples.append((prompt, np.asarray(out)))
    if side is not None:
        side.join()
    prefix1 = engine.health()["decode"].get("prefix", {})
    return {
        "attempted": len(due), "failed": failed, "completed": done,
        "seconds": seconds, "rate_rps": len(due) / seconds,
        "serve_tokens_per_s": tokens_in_window / seconds,
        "ttft_mean_ms": 1e3 * float(np.mean(ttft)),
        "ttft_p95_ms": 1e3 * percentile(ttft, 95),
        "ttft_p90_ms": 1e3 * percentile(ttft, 90),
        "ttft_p50_ms": 1e3 * percentile(ttft, 50),
        "itl_p95_ms": 1e3 * percentile(gaps, 95) if gaps else float("nan"),
        "itl_p50_ms": 1e3 * percentile(gaps, 50) if gaps else float("nan"),
        "generator_lag_p95_ms": 1e3 * percentile(lag, 95),
        "backlog_at_end": health_end["queue_depth"],
        "active_at_end": health_end["active"],
        "kv_pages_used_at_end": health_end["kv_pages_used"],
        "kv_occupancy_at_end": health_end["kv_occupancy"],
        "drain_s": time.perf_counter() - end,
        "histograms": hist,
        "prefix": {k: prefix1.get(k, 0) - prefix0.get(k, 0)
                   for k in ("kv_hit_pages", "kv_miss_pages")},
        "samples": samples,
    }


def run(ctx):
    from paddle_tpu import observability as obs

    cfg, mix = ctx.config, ctx.traffic
    model = ctx.registry.module("models", cfg["model"])
    reference = ctx.registry.reference(cfg["name"])
    params, meta = model.make_params(cfg, ctx.seed)
    t = time.perf_counter()
    engine = model.build_engine(cfg, params, meta, mix["output_len"]["max"])
    ctx.log("serve: engine warmed up in %.1f s" % (time.perf_counter() - t))
    trace = {}

    def tracer(t0):
        def body():
            time.sleep(max(0.0, t0 + mix["trace_after_share"] * ctx.seconds
                           - time.perf_counter()))
            ctx.tracer.start()
            steps0 = obs.histogram("serving.decode.step").snapshot()
            time.sleep(mix["trace_s"])
            trace["steps"] = (obs.histogram("serving.decode.step").snapshot()
                              - steps0).count
            trace["trace"] = ctx.tracer.stop()
        th = threading.Thread(target=body, name="chipbench-tracer")
        th.start()
        return th

    try:
        compiles0 = ctx.compiles()
        setup_s = ctx.since_start()
        w = window(engine, mix, cfg["vocab"], ctx.seconds, ctx.seed,
                   during=tracer if ctx.trace else None)
        compiles = ctx.compiles() - compiles0
        pages_left = engine.health()["decode"]["kv_pages_used"]
    finally:
        engine.stop()
    ctx.log("serve: %d due, %d completed, %d failed; %.1f tokens/s; ttft mean "
            "%.1f p50 %.1f p90 %.1f p95 %.1f ms; itl p50 %.2f p95 %.2f ms; "
            "generator lag p95 %.2f ms; at the window's end backlog %d, %d "
            "active, %d KV pages in use (%.1f%% of the pool); drain %.1f s"
            % (w["attempted"], w["completed"], w["failed"],
               w["serve_tokens_per_s"], w["ttft_mean_ms"], w["ttft_p50_ms"],
               w["ttft_p90_ms"], w["ttft_p95_ms"], w["itl_p50_ms"],
               w["itl_p95_ms"], w["generator_lag_p95_ms"], w["backlog_at_end"],
               w["active_at_end"], w["kv_pages_used_at_end"],
               100.0 * w["kv_occupancy_at_end"], w["drain_s"]))
    bad = []
    errs = model.paged_kernel_errors(cfg, ctx.seed, reference)
    if not all(e <= model.PAGED_RTOL.get(k, 0.0) for k, e in errs.items()):
        bad.append("paged kernels vs reference: %s" % errs)
    gaps = model.token_gaps(cfg, params, w["samples"], reference)
    if len(w["samples"]) < min(mix["checked_requests"], w["attempted"]) or \
            not all(g <= model.TIE_TOL for g in gaps):
        bad.append("first, middle and last tokens vs the f32 reference, gaps "
                   "in logit std: %s" % gaps)
    if w["failed"]:
        bad.append("%d requests failed or came back short" % w["failed"])
    if pages_left:
        bad.append("%d KV pages in use after the drain" % pages_left)
    if compiles:
        bad.append("%d compile events inside the window" % compiles)
    ctx.log("serve: paged kernel errors %s; token gaps %s" % (errs, gaps))
    for b in bad:
        ctx.log("serve: NOT CORRECT: " + b)
    return {
        "correct": not bad, "attempted": w["attempted"], "failed": w["failed"],
        "end_to_end": {"serve_tokens_per_s": w["serve_tokens_per_s"],
                       "itl_p95_ms": w["itl_p95_ms"], "setup_s": setup_s},
        "observed": dict(w, trace=trace.get("trace"),
                         traced_steps=trace.get("steps"),
                         compiles_in_window=compiles),
    }
