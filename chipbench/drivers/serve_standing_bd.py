"""The standing closed loop (``drivers/serve_standing.py``: every request sent
through ``InferenceEngine.generate_async`` during SET-UP, the window opens
when each has its first DELIVERED token, nothing arrives in it, all slots
decode, then the requests are cancelled) for a model that generates by
DIFFUSION OVER BLOCKS: a decode step carries a block of positions a slot and
delivers 0 to ``block_length`` tokens.  The loop, the clocks and the stamps are
that file's (a stamp a delivered token, several may share one instant, so
``serve_tokens_per_s`` and ``itl_p95_ms`` mean what they mean there); the
counters are this family's (``serving.decode.diffusion.*``,
``serving.decode.moe.*``) and ``correct`` is decided on BLOCKS: none of the
loops that are there can replay a forward whose input is a partly masked
block, and a benchmark file that is there is not edited.

``correct``, on what the timed engine produced: for ``checked_requests``
requests and the first, a middle and the last whole block each was served, the
step functions denoise the block again on the served context AND THE SERVED
TRAJECTORY (``model.replay``: where a forward unmasks a position, the id the
window served there is seated): every forward's logits ``[B, V]`` against the
float32 reference run on the same ids over the same experts, THE SERVED IDS
against the reference's top under the logits of the forward that wrote them
(``ids_agree``: what the 64 live slots, the step in flight and the state
carried on the device produced, held to the reference in their SHARE, as
``serve_standing_moe.py`` holds served tokens: the timed engine's top-8 choice
is its own and the host never sees it), the set unmasked against the
reference's (unless the confidences lie within the tie tolerance), the routed
sets, the block closed as it was served; the K and V rows the K/V-writing
forward left against the reference's.  After the drain the engine's OWN
executables run one block of the first checked request once more into its OWN
cache (``model.served_state``): the states they return and the rows they
leave are held to a free-running replay's and the reference's.  Then the
kernels stand-alone, the drain, and no compile in the window.  Every parameter comes from the
configuration's and the mix's files; the model's builder is
``models/<config.model>.py``."""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from chipbench import traffic
from chipbench.drivers.serve import percentile
from chipbench.drivers.serve_standing import _checked

HISTOGRAMS = ("serving.decode.queue_wait", "serving.decode.step",
              "serving.decode.prefill", "serving.decode.block",
              "serving.decode.tokens_delivered")
COUNTERS = ("serving.decode.diffusion.forwards",
            "serving.decode.diffusion.kv_forwards",
            "serving.decode.diffusion.unmasked",
            "serving.decode.diffusion.kv_rows_read",
            "serving.decode.moe.pairs", "serving.decode.moe.experts_touched",
            "serving.decode.moe.max_load", "serving.decode.tokens_discarded",
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
    # ids over the vocabulary less the mask id
    reqs = [(model.prompt_ids(cfg, p), n) for p, n in traffic.requests(
        mix, mix["requests"], ctx.seed, cfg["vocab_size"] - 1)]
    trace = {}
    bad = []
    try:
        # ---- set-up: every request in, every one to its first delivered token
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

        # ---- the window: nothing arrives, every slot denoises
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
        # every slot has a forward a step, but a block's ids are delivered in
        # ORDER, up to all of them by its last denoising forward: each edge of
        # the window cuts a slot's block with 0 to B of its ids delivered, so
        # two sound slots differ by up to two blocks; further behind is a
        # request that fell behind
        B = cfg["block_length"]
        failed = sum(1 for early, n, f in zip(ended_early, in_window, futures)
                     if early or not f.token_times or n < most - 2 * B)
        reached = sum(1 for (_, n), out in zip(reqs, served) if len(out) >= n)

        # ---- correct, on the object that was timed: the engine's own step
        # programs once more into its own cache
        engine.stop()
        checked = _checked(reqs, served, ctx.seed, mix["checked_requests"])
        i0 = checked[0]
        blocks0 = model.checked_blocks(cfg, len(reqs[i0][0]), len(served[i0]))
        held = (model.served_state(cfg, engine.decoder, reqs[i0][0],
                                   served[i0], blocks0[len(blocks0) // 2])
                if blocks0 else [])
    finally:
        engine.stop()
    # the engine is a cycle (scheduler <-> worker <-> futures): collect it
    # now, so that its pool is gone before the checks build one of their own
    attempted = len(futures)
    del engine, futures, f
    gc.collect()
    tokens_per_s = sum(in_window) / ctx.seconds
    itl_p95 = 1e3 * percentile(gaps, 95) if gaps else float("nan")
    ctx.log("standing: %d of %d requests denoised through the %.0f s window "
            "(%d tokens each at most, %d reached their length); %.1f "
            "tokens/s; itl p50 %.2f p95 %.2f ms; at the window's end %d "
            "active, %d pages in use (%.1f%% of the pool); %d pages in use "
            "after the cancel"
            % (attempted - failed, attempted, ctx.seconds, most, reached,
               tokens_per_s, 1e3 * percentile(gaps, 50) if gaps else 0.0,
               itl_p95, health_end["active"], health_end["kv_pages_used"],
               100.0 * health_end["kv_occupancy"], pages_left))

    # ---- correct, against the reference: the engine's pool given back
    errs = model.paged_kernel_errors(cfg, params, ctx.seed, reference)
    if not all(e <= model.PAGED_RTOL.get(k, 0.0) for k, e in errs.items()
               if k not in model.NOT_JUDGED):
        bad.append("kernels vs reference: %s" % errs)
    checks, fns = [], model.replay_fns(cfg)

    def agreeing(gaps):
        return (float(np.mean(np.asarray(gaps) <= model.TIE_TOL))
                if gaps else None)

    state = {}
    for i in checked:
        prompt, out = reqs[i]
        prompt, out = np.asarray(prompt), served[i]
        blocks = model.checked_blocks(cfg, len(prompt), len(out))
        if not blocks:
            bad.append("request %d served %d tokens: no whole block to check"
                       % (i, len(out)))
            continue
        seq = np.concatenate([prompt, out])
        records = model.replay(cfg, params, prompt, out, blocks, fns)
        logit_err, unmask_gap, id_gap, shifted, agree = [], [], [], [], []
        as_served, other_slot, ref_rows = [], [], {}
        # what a block's state landed in the wrong slot would read: the ids
        # another request was served at the same place of its answer
        other = served[(i + 1) % len(served)]
        for n, r in enumerate(records):
            b = r["block"]
            ref_logits, ref_chosen, rows = model.reference_forward(
                cfg, params, seq[:b * B], r["ids"], reference,
                sets=r["extra"]["sets"])
            j = model.judge_forward(cfg, r, ref_logits, reference)
            logit_err.append(j["logit_err"])
            shifted.append(float(np.max(np.abs(
                r["extra"]["logits"][:-1] - ref_logits[1:]))
                / ref_logits.std()))
            agree.append(model.routing_agreement(
                np.concatenate(r["extra"]["sets"]),
                np.concatenate(ref_chosen)))
            if r["kv"]:
                ref_rows[b] = rows
                # the block as the replay finished it against what was served
                as_served.append(float(np.mean(
                    r["ids"] == seq[b * B:(b + 1) * B])))
            else:
                unmask_gap.append(j["unmask_gap"])
                id_gap.extend(model.judge_ids(
                    r, records[n + 1]["ids"], ref_logits))
                at = b * B - len(prompt)
                if 0 <= at and at + B <= len(other):
                    other_slot.extend(model.judge_ids(
                        r, other[at:at + B], ref_logits))
        rows = model.row_errors(cfg, records, ref_rows)
        if i == i0 and held:
            # the engine's own programs run free (their state is carried on
            # the device): held to the step functions run free alike, and to
            # the reference over the block THEY close
            mid = held[0]["block"]
            mine = model.replay(cfg, params, prompt, out, [mid], fns,
                                follow=False)
            _, _, rows_mid = model.reference_forward(
                cfg, params, seq[:mid * B], mine[-1]["ids"], reference,
                sets=mine[-1]["extra"]["sets"])
            state = {"engine_" + k: v for k, v in model.row_errors(
                cfg, held, {mid: rows_mid}).items()}
            state["state_mismatch"] = float(
                abs(len(held) - len(mine)) + sum(
                    int(np.sum(a["ids"] != b["ids"]))
                    + (sorted(a["unmasked"]) != sorted(b["unmasked"]))
                    + (a["kv"] != b["kv"]) for a, b in zip(held, mine)))
        checks.append({
            "request": i, "context": len(prompt), "served": len(out),
            "blocks": blocks, "forwards": len(records),
            "logit_err": max(logit_err), "unmask_gap": max(unmask_gap),
            "ids_agree": agreeing(id_gap), "id_gap": max(id_gap),
            "block_as_served": min(as_served),
            "ids_agree_other_slot": agreeing(other_slot),
            "id_gap_other_slot": min(other_slot) if other_slot else None,
            "logits_shifted": max(shifted),
            "routing": [min(a[0] for a in agree), min(a[1] for a in agree)],
            "rows": rows})
        if not max(logit_err) <= model.LOGIT_TOL:
            bad.append("request %d: a forward's logits vs the f32 reference "
                       "over the same ids and experts, max error in logit "
                       "std: %s" % (i, logit_err))
        if not max(unmask_gap) <= model.TIE_TOL:
            bad.append("request %d: a forward's unmasked set vs the "
                       "reference's, confidence gaps: %s" % (i, unmask_gap))
        if not agreeing(id_gap) >= model.IDS_AGREE:
            bad.append("request %d: share of the ids the window served "
                       "within %s logit std of the reference's top under the "
                       "forward that wrote them, gaps: %s"
                       % (i, model.TIE_TOL, id_gap))
        if not min(as_served) == 1.0:
            bad.append("request %d: a checked block closed other than it was "
                       "served (share of its ids equal): %s" % (i, as_served))
        if not min(a[0] for a in agree) >= model.ROUTING_AGREE:
            bad.append("request %d: routed experts vs the reference's (share "
                       "held, sets equal): %s" % (i, agree))
        if not all(e <= model.SERVED_STATE_TOL.get(k, 0.0)
                   for k, e in rows.items() if k not in model.NOT_JUDGED):
            bad.append("request %d: the K/V rows a whole block's forward left "
                       "vs the reference's: %s" % (i, rows))
    judged = {k: v for k, v in state.items()
              if k.replace("engine_", "") not in model.NOT_JUDGED}
    if not ({"state_mismatch", "engine_kv_rows", "engine_kv_rows_deep"}
            <= set(judged)
            and all(e <= model.SERVED_STATE_TOL.get(
                k.replace("engine_", ""), 0.0) for k, e in judged.items())):
        bad.append("the engine's own programs on its own cache: %s" % state)
    if failed:
        bad.append("%d requests ended, failed or fell behind before the "
                   "window's end" % failed)
    if reached:
        bad.append("%d requests reached their length inside the window"
                   % reached)
    if pages_left:
        bad.append("%d pages in use after the cancel and drain" % pages_left)
    if compiles:
        bad.append("%d compile events inside the window" % compiles)
    ctx.log("standing: served state %s; kernel errors %s; checks %s"
            % (state, errs, checks))
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
            "served_state": state, "kernel_errors": errs,
        },
    }
