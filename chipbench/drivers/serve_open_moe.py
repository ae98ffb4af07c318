"""The open loop of ``drivers/serve.py`` (requests through
``InferenceEngine.generate_async`` at the rate the mix fixes, the client's own
stamps; ``window`` is that file's, so ``chipbench/sweep.py`` sweeps this cell
as it sweeps that one) for a model whose cache is in page GROUPS, one with a
sliding window, and whose feed-forward blocks are a SHARE of routed experts:
``serve.window``, the program's counters at the window's EDGES, and
``moe_check.check``.

* The counters: every ``serving.decode.*`` and ``serving.cache.*`` counter,
  labels and all (the family's step counters of the decode steps and of the
  chunk programs apart, each page group's pages handed out and given back,
  admissions that waited for a group's pages), handed to the per-layer
  readers as ``observed["window_counters"]``; and, in a traced run, the same
  counters at the EDGES OF THE TRACE (``observed["traced_counters"]``): below
  the knee the seated slots swing between ten and sixty inside a window, so
  what a mean program of the traced three seconds moved is not what a mean
  program of the window did, and a share of a roofline divides the one by
  the other's time only if both come from the same stretch.
* ``correct`` (``chipbench/moe_check.py``), on what this loop timed: after
  the drain every group's pages are free; the engine's OWN executables prefill
  a served request's sequence again into the engine's OWN cache, whose window
  pages the window's requests took and gave back before; then the mechanisms
  stand-alone, and for ``checked_sequences`` served requests (the longest
  prompt, whose ring wrapped, and others under the window whose answers are
  long enough to check) the served tokens, the logits and the routed sets
  against the plain reference.

Every parameter comes from the configuration's and the mix's files; the
model's builder is ``models/<config.model>.py``."""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from chipbench import moe_check
from chipbench.drivers.serve import window  # noqa: F401 — sweep.py's door

PREFIXES = ("serving.decode.", "serving.cache.")
PAD = 2048      # a checked sequence is padded to a multiple for the reference


def _counters():
    from paddle_tpu import observability as obs

    return {name: c.value
            for name, c in obs.get_telemetry().counters().items()
            if name.startswith(PREFIXES)}


def _checked(samples, seed, n, under, least):
    """The served requests that are checked: the longest prompt, then by the
    seed among those whose sequence stays under ``under`` positions, those
    whose answer has ``least`` tokens or more before the others."""
    order = [int(np.argmax([len(p) for p, _ in samples]))]
    rng = np.random.RandomState(seed % (2 ** 32))
    fits = [int(i) for i in rng.permutation(len(samples))
            if int(i) not in order and sum(map(len, samples[int(i)])) <= under]
    fits.sort(key=lambda i: len(samples[i][1]) < least)       # stable
    return [samples[i] for i in order + fits[:max(0, n - 1)]]


def run(ctx):
    from paddle_tpu import observability as obs

    cfg, mix = ctx.config, ctx.traffic
    model = ctx.registry.module("models", cfg["model"])
    reference = ctx.registry.reference(cfg["name"])
    params, meta = model.make_params(cfg, ctx.seed)
    t = time.perf_counter()
    engine = model.build_engine(cfg, params, meta, mix["output_len"]["max"])
    ctx.log("open: engine warmed up in %.1f s" % (time.perf_counter() - t))
    trace, edge = {}, {}

    def beside(t0):
        """Beside the window: the tracer where the run is traced, and the
        counters at the window's END (``window`` returns after the drain)."""
        def body():
            if ctx.trace:
                time.sleep(max(0.0, t0 + mix["trace_after_share"] * ctx.seconds
                               - time.perf_counter()))
                ctx.tracer.start()
                steps0 = obs.histogram("serving.decode.step").snapshot()
                traced0 = _counters()
                time.sleep(mix["trace_s"])
                traced1 = _counters()
                trace["steps"] = (obs.histogram("serving.decode.step")
                                  .snapshot() - steps0).count
                trace["trace"] = ctx.tracer.stop()
                trace["counters"] = {k: traced1[k] - traced0.get(k, 0)
                                     for k in traced1}
            time.sleep(max(0.0, t0 + ctx.seconds - time.perf_counter()))
            edge["end"] = _counters()
        th = threading.Thread(target=body, name="chipbench-beside")
        th.start()
        return th

    try:
        compiles0 = ctx.compiles()
        count0 = _counters()
        setup_s = ctx.since_start()
        w = window(engine, mix, cfg["vocab_size"], ctx.seconds, ctx.seed,
                   during=beside)
        count1 = edge["end"]
        compiles = ctx.compiles() - compiles0
        engine.stop()
        groups = engine.decoder.cache_stats()["groups"]
        checked = _checked(w["samples"], ctx.seed, mix["checked_sequences"],
                           cfg["sliding_window"],
                           model.CHECKED_TOKENS // 2) if w["samples"] else []
        state = ({}, None) if not checked else moe_check.served_state(
            model, cfg, engine.decoder, checked[0], ctx.seed, params,
            reference)
    finally:
        engine.stop()
    # the engine is a cycle (scheduler <-> worker <-> futures): collect it
    # now, so that its pools are gone before the checks build their own
    del engine
    gc.collect()
    moved = {k: count1[k] - count0.get(k, 0) for k in count1}
    waits = {k: v for k, v in moved.items() if "admit_waits_for_pages" in k}
    ctx.log("open: %d due, %d completed, %d failed; %.1f tokens/s; ttft mean "
            "%.1f p50 %.1f p95 %.1f ms; itl p50 %.2f p95 %.2f ms; generator "
            "lag p95 %.2f ms; at the window's end backlog %d, %d active, %d "
            "full pages in use (%.1f%% of the group); drain %.1f s; admissions "
            "that waited for pages %s; groups after the drain %s"
            % (w["attempted"], w["completed"], w["failed"],
               w["serve_tokens_per_s"], w["ttft_mean_ms"], w["ttft_p50_ms"],
               w["ttft_p95_ms"], w["itl_p50_ms"], w["itl_p95_ms"],
               w["generator_lag_p95_ms"], w["backlog_at_end"],
               w["active_at_end"], w["kv_pages_used_at_end"],
               100.0 * w["kv_occupancy_at_end"], w["drain_s"], waits,
               {g: {k: st[k] for k in ("used_pages", "taken_pages",
                                       "released_pages")}
                for g, st in groups.items()}))

    # ---- correct, against the reference: the engine's pools given back
    bad, checks, errs, held = moe_check.check(
        model, cfg, params, reference, ctx.seed, checked, state,
        pad=(PAD, cfg["sliding_window"]))
    if len(checked) < min(mix["checked_sequences"], w["attempted"]):
        bad.append("%d served sequences to check, %d wanted"
                   % (len(checked), mix["checked_sequences"]))
    if w["failed"]:
        bad.append("%d requests failed or came back short" % w["failed"])
    left = {g: st["used_pages"] + st.get("reserved_pages", 0)
            for g, st in groups.items()}
    if any(left.values()):
        bad.append("pages in use or reserved after the drain: %s" % left)
    if compiles:
        bad.append("%d compile events inside the window" % compiles)
    ctx.log("open: served state %s; mechanism errors %s; checks %s"
            % (held, errs, checks))
    for b in bad:
        ctx.log("open: NOT CORRECT: " + b)
    return {
        "correct": not bad, "attempted": w["attempted"], "failed": w["failed"],
        "end_to_end": {"serve_tokens_per_s": w["serve_tokens_per_s"],
                       "itl_p95_ms": w["itl_p95_ms"], "setup_s": setup_s},
        "observed": dict(
            {k: v for k, v in w.items() if k != "samples"},
            window_counters=moved, groups_after_drain=groups,
            trace=trace.get("trace"), traced_steps=trace.get("steps"),
            traced_counters=trace.get("counters"),
            compiles_in_window=compiles, checks=checks, served_state=held),
    }
