"""Open-loop SLO load harness: Poisson/bursty arrivals, goodput by class.

A CLOSED-loop load is clients that wait for an answer before sending the
next request, so offered load self-throttles to whatever the engine
serves.  "Millions of users" do
not behave like that — arrivals are an OPEN-loop process that keeps
coming whether or not the engine keeps up, and the question stops being
"how many requests/s" and becomes "what fraction of requests get a
useful (within-deadline) answer, per priority class, while the engine is
offered more than it can serve".  That is Clipper's framing (Crankshaw
et al., NSDI'17): latency SLOs, shed-at-admission, goodput-under-
deadline.

What this harness does, per leg:

1. derive a deterministic arrival schedule from ``--seed``: Poisson
   (exponential gaps) or bursty (Poisson modulated by an on/off cycle,
   4x the rate in bursts, 0.25x between) at ``overload`` x the engine's
   measured closed-loop capacity;
2. assign each arrival a priority class (30% interactive / 40% batch /
   30% best_effort) and a per-class deadline scaled to the measured
   service rate, so the same scenario stresses a fast laptop and a
   2-core CI runner identically;
3. submit ``predict_async`` AT the scheduled instant, never waiting for
   results (open loop!) — typed rejections (``ServingQueueFull`` /
   ``ServingOverloaded`` / ``ServingDegraded``) are recorded as sheds;
4. resolve every admitted future and report, per class: attempted /
   admitted / shed / expired / failed / ok, goodput-under-deadline
   (within-deadline answers over ATTEMPTED — sheds count against, as in
   Clipper), and p50/p95/p99 latency of answered requests.

Every leg runs inside a ``faults.slow_execute`` shim that adds a fixed
per-dispatch service delay: it makes the engine's capacity dominated by
a known constant instead of host CPU speed (deterministic overload on
any machine) and stands in for the accelerator round trip that a real
deployment's dispatch would pay.  The ``faulty`` legs nest real chaos on
top (``flaky_execute`` transient faults) to measure SLOs *during*
failures — retry/bisection keeps goodput nonzero where a naive engine
would fail every co-batched request.

Smoke mode (the CI gate via tools/check_slo.py) asserts the structural
truths that must survive any machine: every admitted request reaches a
terminal outcome (no hangs), overload actually shed something, the
priority ladder holds (interactive goodput strictly above best_effort),
and transient faults were retried without losing requests.

Multi-replica serving (``serving.ReplicaPool``) rides the same harness:
``--replicas N`` serves every leg from an N-replica pool over forced
host devices instead of a single engine (same admission surface, so
nothing else changes), ``--decode`` adds a MIXED leg per arrival
process — every ``DECODE_EVERY``-th arrival becomes a
``generate_async`` call riding the pool's durable decode path
(per-replica ``DecodeScheduler``s behind the shared queue,
docs/fault_tolerance.md "Decode durability") while the rest stay
predicts, smoke-asserting zero unresolved futures across BOTH kinds
and the interactive > best_effort goodput ladder under the mixed
load — and ``--scaling`` runs the replica-scaling
ladder — ONE warm 4-replica pool whose ACTIVE rotation is resized
1 → 2 → 4 between legs (``set_active_replicas``, i.e. the autoscale
path under live traffic), all legs offered the SAME fixed rate derived
from the measured 1-replica capacity.  Because the ``slow_execute``
shim makes per-dispatch service time a sleep-dominated constant, the
ladder is machine-independent: per-class goodput is reported per
rotation size, and smoke mode asserts aggregate within-deadline answers
at N=4 >= 2.5x N=1 (the tier-1 scaling floor, gated via
tools/check_replica_pool.py).

Usage:
  python benchmarks/bench_load.py             # full run, prints JSON
  python benchmarks/bench_load.py --smoke     # quick run + assertions
  python benchmarks/bench_load.py --process bursty --overload 5
  python benchmarks/bench_load.py --replicas 4 --smoke
  python benchmarks/bench_load.py --replicas 4 --decode --smoke
  python benchmarks/bench_load.py --scaling --smoke
  python benchmarks/bench_load.py --multi-model --smoke
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

WIDTH = 64
CLASSES = 10
SERVICE_DELAY_S = 0.02      # injected per-dispatch cost (see module doc)
QUEUE_CAPACITY = 256
# reserve headroom for the interactive lane: batch+best_effort together
# can hold at most ~60% of the queue, so sustained low-priority overload
# can never queue_full-starve interactive admission
CLASS_CAPACITY = {"batch": 96, "best_effort": 64}
CLASS_MIX = (("interactive", 0.30), ("batch", 0.40), ("best_effort", 0.30))
# deadlines as multiples of the measured mean per-request service time
# (rows/s is machine-dependent; the ladder shape is not).  best_effort's
# deadline sits just UNDER its full-lane queue wait, so once the
# service-rate estimator is warm those arrivals shed AT ADMISSION
# (ServingOverloaded) instead of being discovered dead at pop time.
DEADLINE_ROWS = {"interactive": 120, "batch": 240, "best_effort": 120}
# --decode mixed legs: every Nth arrival is a generation instead of a
# predict (offset 3 so the first few arrivals warm the predict path)
DECODE_EVERY = 7
DECODE_NEW_TOKENS = 6


def save_model(dirname):
    import paddle_tpu as fluid

    main = fluid.Program()
    startup = fluid.Program()
    startup.random_seed = 1234
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[WIDTH], dtype="float32")
            h = fluid.layers.fc(x, size=WIDTH, act="relu")
            out = fluid.layers.fc(h, size=CLASSES, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        np.random.seed(7)
        exe.run(startup)
        fluid.io.save_inference_model(dirname, ["x"], [out], exe,
                                      main_program=main)
    return dirname


def build_decode_model():
    """Small 2-layer LM for the ``--decode`` mixed legs (same shape the
    decode gates use: fast to warm, real paged-KV decode path)."""
    from paddle_tpu.models import transformer as T

    params, meta = T.lm_params(seed=31, vocab_size=60, n_layer=2,
                               n_head=2, d_model=32, d_inner=64,
                               max_length=128)
    return T.build_decode_model(params, meta)


def make_engine(model_dir, replicas=1, max_replicas=None, decode=False,
                session_mix=False):
    """One serving frontend: a single engine (``replicas=1``) or an
    N-replica pool — same admission surface, so every leg below is
    agnostic to which it got.  ``decode=True`` attaches a decode model
    so the mixed legs can route ``generate_async`` through the pool;
    ``session_mix=True`` additionally turns on the prefix cache so the
    pool auto-creates a SessionStore (the --session-mix legs tag their
    decode arrivals with conversation ids)."""
    from paddle_tpu import serving

    decode_kw = {}
    if decode:
        decode_kw = dict(
            decode_model=build_decode_model(),
            decode_config=serving.DecodeConfig(
                num_slots=4, page_size=8, max_seq_len=64,
                max_new_tokens=DECODE_NEW_TOKENS,
                prefill_chunk_tokens=16 if session_mix else None,
                prefix_cache=bool(session_mix)))
    if replicas == 1 and max_replicas is None and not decode:
        return serving.InferenceEngine(
            model_dir, batch_buckets=(2, 4, 8, 16), max_batch_size=16,
            batch_timeout_ms=0.0, queue_capacity=QUEUE_CAPACITY,
            class_capacity=CLASS_CAPACITY, backend="program",
            breaker_threshold=8, breaker_cooldown_s=0.5,
            supervisor_interval_s=0.05)
    return serving.ReplicaPool(
        model_dir, replicas=max_replicas or replicas,
        initial_replicas=replicas,
        batch_buckets=(2, 4, 8, 16), max_batch_size=16,
        batch_timeout_ms=0.0, queue_capacity=QUEUE_CAPACITY,
        class_capacity=CLASS_CAPACITY, backend="program",
        breaker_threshold=8, breaker_cooldown_s=0.5,
        supervisor_interval_s=0.05, **decode_kw)


def measure_capacity(engine, seconds=1.0, n_threads=4, depth=8):
    """Closed-loop requests/s with the service-delay shim active — the
    ceiling the open-loop legs overload against."""
    rng = np.random.RandomState(99)
    payloads = [rng.randn(1, WIDTH).astype(np.float32) for _ in range(64)]
    stop = time.perf_counter() + seconds
    counts = [0] * n_threads
    errors = []

    def client(t):
        try:
            while time.perf_counter() < stop:
                futs = [engine.predict_async({"x": payloads[(t + k) % 64]})
                        for k in range(depth)]
                for f in futs:
                    f.result(timeout=30)
                counts[t] += depth
        except BaseException as e:  # noqa: BLE001 - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(t,))
               for t in range(n_threads)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return sum(counts) / (time.perf_counter() - t0)


def build_schedule(process, rate, n, seed, capacity):
    """Deterministic arrival schedule: [(t_offset_s, class, deadline_ms)].

    ``poisson``: exponential inter-arrival gaps at ``rate``.
    ``bursty``: the same, but the rate is modulated by a 0.25s on /
    0.25s off cycle (4x during bursts, 0.25x between) — same mean rate,
    much spikier queue.
    """
    rng = np.random.RandomState(seed)
    names = [c for c, _ in CLASS_MIX]
    probs = np.asarray([p for _, p in CLASS_MIX])
    classes = rng.choice(len(names), size=n, p=probs / probs.sum())
    per_req_s = 1.0 / max(capacity, 1e-6)
    t, sched = 0.0, []
    for i in range(n):
        if process == "bursty":
            phase_rate = rate * (4.0 if (t % 0.5) < 0.25 else 0.25)
        else:
            phase_rate = rate
        t += rng.exponential(1.0 / phase_rate)
        cls = names[int(classes[i])]
        deadline_ms = max(50.0, DEADLINE_ROWS[cls] * per_req_s * 1e3)
        sched.append((t, cls, deadline_ms))
    return sched


def run_open_loop(engine, schedule, seed, decode_every=0,
                  session_mix=0):
    """Submit the schedule open-loop; resolve everything; per-class
    outcome table.  Returns (per_class dict, overall dict).

    ``decode_every=N``: every Nth arrival becomes a ``generate_async``
    call (a short generation through the pool's decode schedulers, same
    priority class, no deadline) instead of a predict — the mixed
    predict+generate traffic shape a real LM frontend serves.  Generate
    outcomes are tallied separately under ``overall["generate"]``; the
    per-class predict table keeps its meaning.

    ``session_mix=K``: the decode arrivals cycle over K live
    conversations — arrival j carries ``session="conv-<j mod K>"`` and
    that conversation's FIXED prompt, so repeated turns of the same
    conversation hit its session-pinned KV pages and sticky affinity
    routes them to the owning replica (the conversational traffic
    shape; serving/sessions.py).

    Latency quantiles come from the LIVE telemetry histograms
    (``serving.request_latency_<class>``, snapshotted before/after the
    leg and diffed) — the bench reports the same numbers a Prometheus
    scrape of ``/metrics`` would show for the same window, by
    construction, instead of a second sort-based percentile
    implementation that could drift from it."""
    from paddle_tpu import observability as obs
    from paddle_tpu import serving

    rng = np.random.RandomState(seed + 1)
    payloads = [rng.randn(1, WIDTH).astype(np.float32) for _ in range(128)]
    prompts = [rng.randint(1, 60, size=rng.randint(4, 13)).astype(np.int32)
               for _ in range(64)]
    outcomes = []   # (cls, kind, latency_s or None, deadline_met)
    futs = []       # (idx, cls, deadline_ms, arrival_ts, fut)
    gen_futs = []   # generate requests resolve on their own tally
    gen = {"attempted": 0, "ok": 0, "shed": 0, "failed": 0, "unresolved": 0}
    lateness = []     # exact: not exported anywhere, so no histogram to match
    lat_base = {cls: obs.histogram("serving.request_latency_%s" % cls)
                .snapshot() for cls, _ in CLASS_MIX}
    t0 = time.perf_counter()
    for i, (dt, cls, deadline_ms) in enumerate(schedule):
        now = time.perf_counter() - t0
        if dt > now:
            time.sleep(dt - now)
        else:
            lateness.append(now - dt)
        arrival = time.perf_counter()
        if decode_every and i % decode_every == 3:
            gen["attempted"] += 1
            session_kw = {}
            if session_mix:
                sid = (i // decode_every) % session_mix
                session_kw = dict(session="conv-%d" % sid)
            try:
                gf = engine.generate_async(
                    prompts[sid % 64] if session_mix else prompts[i % 64],
                    max_new_tokens=DECODE_NEW_TOKENS,
                    priority=cls, **session_kw)
            except serving.ServingError:
                gen["shed"] += 1
            else:
                gen_futs.append(gf)
            continue
        try:
            fut = engine.predict_async({"x": payloads[i % 128]},
                                       deadline_ms=deadline_ms,
                                       priority=cls)
        except serving.ServingOverloaded:
            outcomes.append((cls, "shed_admission", None, False))
        except serving.ServingQueueFull:
            outcomes.append((cls, "shed_queue_full", None, False))
        except serving.ServingDegraded:
            outcomes.append((cls, "shed_degraded", None, False))
        else:
            futs.append((i, cls, deadline_ms, arrival, fut))
    submit_span = time.perf_counter() - t0
    for gf in gen_futs:
        try:
            toks = gf.result(timeout=120)
        except serving.ServingError:
            gen["failed"] += 1   # typed terminal outcome (shed at pop,
        else:                    # degraded, cancelled...) — not a hang
            gen["ok"] += 1 if len(toks) else 0
    if session_mix and gen_futs:
        # one CLOSING turn per conversation, after the open-loop storm
        # fully resolved: under overload the storm's turns of one
        # conversation overlap in the queue (turn k+1 admitted before
        # turn k retired and parked), so stickiness there is luck — but
        # by now every conversation is parked, so these turns MUST ride
        # session-sticky affinity onto the replica holding their pins
        close = {"attempted": 0, "ok": 0, "shed": 0, "failed": 0}
        closing = []
        for sid in range(session_mix):
            close["attempted"] += 1
            try:
                closing.append(engine.generate_async(
                    prompts[sid % 64], max_new_tokens=DECODE_NEW_TOKENS,
                    session="conv-%d" % sid))
            except serving.ServingError:
                close["shed"] += 1
        for gf in closing:
            try:
                toks = gf.result(timeout=120)
            except serving.ServingError:
                close["failed"] += 1
            else:
                close["ok"] += 1 if len(toks) else 0
        # tallied apart from gen: closing turns are an epilogue, not
        # part of the leg's scheduled arrivals (the smoke identity
        # resolved == requests must keep holding)
        gen["closing_turns"] = close
    gen["unresolved"] = gen["attempted"] - gen["shed"] - gen["failed"] \
        - gen["ok"]
    unresolved = 0
    for i, cls, deadline_ms, arrival, fut in futs:
        try:
            fut.result(timeout=60)
        except serving.ServingTimeout:
            outcomes.append((cls, "expired", None, False))
        except Exception:  # noqa: BLE001 — a failed request re-raises
            # its original fault (injected IOError, poison ValueError,
            # ServingDegraded...): terminal, typed, counted as failed
            outcomes.append((cls, "failed", None, False))
        else:
            if fut.done_ts is None:   # cannot happen; belt and braces
                unresolved += 1
                continue
            latency = fut.done_ts - arrival
            met = latency * 1e3 <= deadline_ms
            outcomes.append((cls, "ok", latency, met))
    per_class = {}
    for cls, _ in CLASS_MIX:
        rows = [o for o in outcomes if o[0] == cls]
        kinds = {}
        for _, kind, _, _ in rows:
            kinds[kind] = kinds.get(kind, 0) + 1
        n_attempted = len(rows)
        n_good = sum(1 for o in rows if o[3])
        entry = {
            "attempted": n_attempted,
            "ok": kinds.get("ok", 0),
            "ok_within_deadline": n_good,
            "shed_admission": kinds.get("shed_admission", 0),
            "shed_queue_full": kinds.get("shed_queue_full", 0),
            "shed_degraded": kinds.get("shed_degraded", 0),
            "expired": kinds.get("expired", 0),
            "failed": kinds.get("failed", 0),
            "goodput": round(n_good / n_attempted, 4) if n_attempted else None,
        }
        # windowed delta of the live per-class latency histogram: the
        # same estimator (and usually the same observations) a live
        # /metrics scrape reports for this leg
        lat_delta = (obs.histogram("serving.request_latency_%s" % cls)
                     .snapshot() - lat_base[cls])
        for q, name in ((0.50, "p50_ms"), (0.95, "p95_ms"),
                        (0.99, "p99_ms")):
            v = lat_delta.quantile(q)
            entry[name] = None if v is None else round(v * 1e3, 2)
        entry["telemetry_latency_n"] = lat_delta.count
        per_class[cls] = entry
    overall = {
        "requests": len(schedule),
        "admitted": len(futs),
        "unresolved": unresolved,
        "submit_span_s": round(submit_span, 3),
        "offered_rate_req_s": round(len(schedule) / schedule[-1][0], 1),
        "p95_submit_lateness_ms": (
            round(float(np.percentile(lateness, 95)) * 1e3, 2)
            if lateness else 0.0),
    }
    if decode_every:
        overall["generate"] = gen
    return per_class, overall


def run_leg(engine, process, rate, n, seed, capacity, flaky_every=0,
            decode_every=0, session_mix=0):
    from paddle_tpu import observability as obs
    from paddle_tpu.testing import faults

    schedule = build_schedule(process, rate, n, seed, capacity)
    r0 = obs.counter("serving.retries").value
    if flaky_every:
        # fault every Nth dispatch ATTEMPT (not a consecutive burst):
        # each hit is followed by a clean retry, so transient faults are
        # retried to success and goodput survives the chaos
        count = [0]

        def every_nth(requests):
            count[0] += 1
            return count[0] % flaky_every == 0

        with faults.flaky_execute(times=None, match=every_nth):
            per_class, overall = run_open_loop(engine, schedule, seed,
                                               decode_every=decode_every,
                                               session_mix=session_mix)
    else:
        per_class, overall = run_open_loop(engine, schedule, seed,
                                           decode_every=decode_every,
                                           session_mix=session_mix)
    overall["retries"] = obs.counter("serving.retries").value - r0
    overall["process"] = process
    return {"per_class": per_class, "overall": overall}


def run_load_bench(smoke, process, overload, n_requests, seed, replicas=1,
                   decode=False, session_mix=0):
    from paddle_tpu import observability as obs
    from paddle_tpu.testing import faults

    td = tempfile.mkdtemp()
    model_dir = save_model(os.path.join(td, "model"))
    legs = {}
    engine = make_engine(model_dir, replicas=replicas, decode=decode,
                         session_mix=session_mix)
    sticky0 = obs.counter("serving.affinity.sticky").value
    old_switch = sys.getswitchinterval()
    sys.setswitchinterval(0.001)
    try:
        with faults.slow_execute(SERVICE_DELAY_S):
            capacity = measure_capacity(
                engine, seconds=0.5 if smoke else 1.5)
            rate = overload * capacity
            processes = [process] if process else (
                ["poisson"] if smoke else ["poisson", "bursty"])
            attempt = 0
            while True:
                for proc in processes:
                    legs[proc] = run_leg(engine, proc, rate, n_requests,
                                         seed + attempt, capacity)
                    if decode:
                        legs["%s_decode" % proc] = run_leg(
                            engine, proc, rate, n_requests,
                            seed + attempt + 13, capacity,
                            decode_every=DECODE_EVERY,
                            session_mix=session_mix)
                legs["%s_faulty" % processes[0]] = run_leg(
                    engine, processes[0], rate, n_requests,
                    seed + attempt + 7, capacity, flaky_every=7)
                if not smoke or attempt >= 3 or _smoke_ladder_holds(legs):
                    break
                attempt += 1   # shared-CI scheduler stall: one more try
    finally:
        sys.setswitchinterval(old_switch)
        engine.stop()
    out = {
        "model": "mlp 2x%d + %.0fms service shim" % (WIDTH,
                                                     SERVICE_DELAY_S * 1e3),
        "replicas": replicas,
        "decode": decode,
        "capacity_req_s": round(capacity, 1),
        "overload_factor": overload,
        "offered_rate_req_s": round(rate, 1),
        "requests_per_leg": n_requests,
        "seed": seed,
        "legs": legs,
    }
    if session_mix:
        out["session_mix"] = {
            "conversations": session_mix,
            "sticky_affinity_hits":
                obs.counter("serving.affinity.sticky").value - sticky0,
        }
    if smoke:
        _assert_smoke(out)
        if session_mix:
            # structural: conversations actually went sticky, and every
            # tagged generation reached a terminal outcome
            assert out["session_mix"]["sticky_affinity_hits"] > 0, (
                "no decode arrival rode its session's sticky affinity: "
                "%r" % (out["session_mix"],))
            for name, leg in legs.items():
                gen = leg["overall"].get("generate")
                assert gen is None or gen["unresolved"] == 0, (name, gen)
    return out


SCALING_LADDER = (1, 2, 4)


def run_scaling_bench(smoke, overload, n_requests, seed):
    """Replica-scaling ladder: ONE warm pool of ``max(SCALING_LADDER)``
    replicas; for each rung the ACTIVE rotation is resized
    (``set_active_replicas`` — the autoscale path) and the same fixed
    offered rate (``overload`` x the measured 1-replica capacity) is
    replayed open-loop.  Per-class goodput per rung; smoke asserts the
    tier-1 scaling floor — aggregate within-deadline answers at the top
    rung >= 2.5x the bottom rung — which the ``slow_execute`` shim makes
    machine-independent (service time is a sleep, not host CPU)."""
    from paddle_tpu.testing import faults

    td = tempfile.mkdtemp()
    model_dir = save_model(os.path.join(td, "model"))
    top = max(SCALING_LADDER)
    pool = make_engine(model_dir, replicas=min(SCALING_LADDER),
                       max_replicas=top)
    rungs = {}
    old_switch = sys.getswitchinterval()
    sys.setswitchinterval(0.001)
    try:
        with faults.slow_execute(SERVICE_DELAY_S):
            capacity1 = measure_capacity(pool, seconds=0.5 if smoke else 1.5)
            rate = overload * capacity1   # FIXED across rungs
            for n in SCALING_LADDER:
                applied = pool.set_active_replicas(n, reason="bench_ladder")
                assert applied == n, (applied, n)
                rungs["replicas_%d" % n] = run_leg(
                    pool, "poisson", rate, n_requests, seed, capacity1)
                rungs["replicas_%d" % n]["active_replicas"] = n
    finally:
        sys.setswitchinterval(old_switch)
        pool.stop()
    out = {
        "model": "mlp 2x%d + %.0fms service shim" % (WIDTH,
                                                     SERVICE_DELAY_S * 1e3),
        "ladder": list(SCALING_LADDER),
        "capacity_1_replica_req_s": round(capacity1, 1),
        "overload_factor": overload,
        "offered_rate_req_s": round(rate, 1),
        "requests_per_rung": n_requests,
        "seed": seed,
        "rungs": rungs,
    }
    if smoke:
        _assert_scaling_smoke(out)
    return out


# --multi-model: two deployments behind one ModelRouter.  Traffic is
# SKEWED (the front model takes most of it) and the backfill model
# starts COLD — its first arrival, midway through the run, parks while
# the router activates it under live front traffic.  Tenants map 1:1 to
# SLO classes via their quota's slo_class; "greedy" also carries a
# tight token bucket so quota enforcement shows up in the report.
MM_SKEW = 0.75                   # P(arrival -> front deployment)
MM_TENANTS = {"anchor": "interactive", "batchy": "batch",
              "greedy": "best_effort"}
MM_CLASS_TENANT = {v: k for k, v in MM_TENANTS.items()}


def run_multi_model_bench(smoke, overload, n_requests, seed):
    """Multi-model serving-plane leg: one ModelRouter, two deployments
    ("front" warm, "backfill" cold until mid-run), skewed Poisson
    arrivals, per-tenant quotas riding the priority lanes.  Smoke
    asserts the serving-plane contract: zero unresolved futures across
    BOTH deployments (including the parked-then-bound cold ones), the
    greedy tenant really was quota-limited (typed sheds > 0, admissions
    bounded), the cold activation happened mid-run, and interactive
    goodput strictly beats best_effort per deployment."""
    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.testing import faults

    td = tempfile.mkdtemp()
    dirs = {"front": save_model(os.path.join(td, "front")),
            "backfill": save_model(os.path.join(td, "backfill"))}
    router = serving.ModelRouter(
        replica_budget=4, batch_buckets=(2, 4, 8, 16), max_batch_size=16,
        batch_timeout_ms=0.0, queue_capacity=QUEUE_CAPACITY,
        class_capacity=CLASS_CAPACITY, backend="program",
        breaker_threshold=8, breaker_cooldown_s=0.5,
        supervisor_interval_s=0.05, warmup=False)
    router.deploy("front", dirs["front"], replicas=2)
    router.deploy("backfill", dirs["backfill"], replicas=2, warm=False)

    class _Front:   # capacity probe speaks the single-model surface
        @staticmethod
        def predict_async(feed, **kw):
            return router.predict_async("front", feed, **kw)

    old_switch = sys.getswitchinterval()
    sys.setswitchinterval(0.001)
    try:
        with faults.slow_execute(SERVICE_DELAY_S):
            capacity = measure_capacity(_Front, seconds=0.5 if smoke
                                        else 1.5)
            rate = overload * capacity
            # quotas AFTER the probe so it isn't throttled: anchor and
            # batchy are paced just under their fair share; greedy asks
            # for far more than its bucket sustains -> typed sheds
            router.set_quota("anchor", slo_class="interactive")
            router.set_quota("batchy", slo_class="batch")
            router.set_quota("greedy", rows_per_s=max(1.0, capacity * 0.05),
                             burst_rows=8, max_inflight=16,
                             slo_class="best_effort")
            attempt = 0
            while True:
                report = _run_multi_model_leg(
                    router, obs, serving, rate, n_requests,
                    seed + attempt, capacity)
                if not smoke or attempt >= 3 \
                        or _mm_ladder_holds(report["per_deployment"]):
                    break
                attempt += 1   # shared-CI scheduler stall: one more try
                router.deactivate("backfill")   # next leg re-exercises
                # the mid-run cold activation too
    finally:
        sys.setswitchinterval(old_switch)
        router.stop()
    out = {
        "model": "mlp 2x%d + %.0fms service shim" % (WIDTH,
                                                     SERVICE_DELAY_S * 1e3),
        "deployments": {"front": "2 replicas, warm",
                        "backfill": "2 replicas, COLD until mid-run"},
        "skew_front": MM_SKEW,
        "replica_budget": 4,
        "capacity_front_req_s": round(capacity, 1),
        "overload_factor": overload,
        "offered_rate_req_s": round(rate, 1),
        "requests": n_requests,
        "seed": seed,
    }
    out.update(report)
    if smoke:
        _assert_multi_model_smoke(out)
    return out


def _run_multi_model_leg(router, obs, serving, rate, n, seed, capacity):
    rng = np.random.RandomState(seed + 2)
    payloads = [rng.randn(1, WIDTH).astype(np.float32) for _ in range(128)]
    schedule = build_schedule("poisson", rate, n, seed, capacity)
    # deployment per arrival: front-only in the first half (backfill is
    # still cold), skewed mix after the midpoint — the first backfill
    # arrival IS the mid-run cold activation
    deploy_draw = rng.rand(n)
    act0 = obs.counter("serving.router.activations",
                       {"model": "backfill", "version": "v1"}).value
    quota0 = obs.counter("serving.router.quota_rejections",
                         {"model": "front", "tenant": "greedy"}).value \
        + obs.counter("serving.router.quota_rejections",
                      {"model": "backfill", "tenant": "greedy"}).value
    futs, outcomes = [], []
    quota_shed = {t: 0 for t in MM_TENANTS}
    t0 = time.perf_counter()
    for i, (dt, cls, deadline_ms) in enumerate(schedule):
        now = time.perf_counter() - t0
        if dt > now:
            time.sleep(dt - now)
        name = "front" if (i < n // 2 or deploy_draw[i] < MM_SKEW) \
            else "backfill"
        tenant = MM_CLASS_TENANT[cls]
        arrival = time.perf_counter()
        try:
            fut = router.predict_async(name, {"x": payloads[i % 128]},
                                       deadline_ms=deadline_ms,
                                       tenant=tenant)
        except serving.ServingQuotaExceeded:
            quota_shed[tenant] += 1
            outcomes.append((name, cls, "shed_quota", False))
        except (serving.ServingOverloaded, serving.ServingQueueFull,
                serving.ServingDegraded):
            outcomes.append((name, cls, "shed", False))
        else:
            futs.append((name, cls, deadline_ms, arrival, fut))
    unresolved = 0
    for name, cls, deadline_ms, arrival, fut in futs:
        try:
            fut.result(timeout=120)
        except serving.ServingTimeout:
            outcomes.append((name, cls, "expired", False))
        except serving.ServingError:
            outcomes.append((name, cls, "failed", False))
        else:
            done_ts = fut.done_ts
            if done_ts is None:     # cannot happen; belt and braces
                unresolved += 1
                continue
            met = (done_ts - arrival) * 1e3 <= deadline_ms
            outcomes.append((name, cls, "ok", met))
    per_dep = {}
    for name in ("front", "backfill"):
        per_cls = {}
        for cls, _ in CLASS_MIX:
            rows = [o for o in outcomes if o[0] == name and o[1] == cls]
            good = sum(1 for o in rows if o[3])
            per_cls[cls] = {
                "attempted": len(rows),
                "ok": sum(1 for o in rows if o[2] == "ok"),
                "ok_within_deadline": good,
                "shed": sum(1 for o in rows
                            if o[2] in ("shed", "shed_quota")),
                "expired": sum(1 for o in rows if o[2] == "expired"),
                "failed": sum(1 for o in rows if o[2] == "failed"),
                "goodput": round(good / len(rows), 4) if rows else None,
            }
        per_dep[name] = per_cls
    activations = obs.counter("serving.router.activations",
                              {"model": "backfill", "version": "v1"}).value \
        - act0
    quota_rejections = obs.counter(
        "serving.router.quota_rejections",
        {"model": "front", "tenant": "greedy"}).value \
        + obs.counter("serving.router.quota_rejections",
                      {"model": "backfill", "tenant": "greedy"}).value \
        - quota0
    return {
        "per_deployment": per_dep,
        "overall": {
            "requests": n,
            "admitted": len(futs),
            "unresolved": unresolved,
            "quota_shed_by_tenant": quota_shed,
            "quota_rejections_labeled": quota_rejections,
            "backfill_cold_activations": activations,
            "submit_span_s": round(time.perf_counter() - t0, 3),
        },
    }


def _mm_ladder_holds(per_dep):
    for per_cls in per_dep.values():
        gi = per_cls["interactive"]["goodput"] or 0.0
        gb = per_cls["best_effort"]["goodput"]
        if gb is None:
            continue
        if not gi > gb:
            return False
    return True


def _assert_multi_model_smoke(report):
    ov = report["overall"]
    # (no hangs) every admitted future — including the parked-then-
    # bound cold ones — reached a terminal outcome
    assert ov["unresolved"] == 0, ov
    total = sum(c["attempted"] for d in report["per_deployment"].values()
                for c in d.values())
    assert total == ov["requests"], (total, ov)
    # the cold deployment really activated mid-run, under live traffic
    assert ov["backfill_cold_activations"] >= 1, ov
    backfill = report["per_deployment"]["backfill"]
    assert sum(c["ok"] for c in backfill.values()) > 0, backfill
    # per-tenant quota enforcement: the greedy tenant was shed typed
    # (and the labeled router counter agrees), the paced tenants never
    assert ov["quota_shed_by_tenant"]["greedy"] > 0, ov
    assert ov["quota_rejections_labeled"] == \
        ov["quota_shed_by_tenant"]["greedy"], ov
    assert ov["quota_shed_by_tenant"]["anchor"] == 0, ov
    assert ov["quota_shed_by_tenant"]["batchy"] == 0, ov
    # the priority ladder holds per deployment: interactive strictly
    # beats best_effort on goodput-under-deadline wherever both ran
    for name, per_cls in report["per_deployment"].items():
        gi = per_cls["interactive"]["goodput"]
        gb = per_cls["best_effort"]["goodput"]
        if gb is None:
            continue
        assert gi is not None and gi > gb, (
            "priority ladder inverted on %s: interactive %s <= "
            "best_effort %s" % (name, gi, gb))


def _good_total(leg):
    return sum(c["ok_within_deadline"] for c in leg["per_class"].values())


def _assert_scaling_smoke(report):
    rungs = report["rungs"]
    for name, leg in rungs.items():
        assert leg["overall"]["unresolved"] == 0, (name, leg["overall"])
    lo = rungs["replicas_%d" % min(SCALING_LADDER)]
    hi = rungs["replicas_%d" % max(SCALING_LADDER)]
    g_lo, g_hi = _good_total(lo), _good_total(hi)
    assert g_lo > 0, "1-replica rung answered nothing within deadline"
    # the tier-1 scaling floor (tools/check_replica_pool.py): under a
    # fixed offered rate that overloads one replica, 4 replicas must
    # deliver >= 2.5x the within-deadline answers
    assert g_hi >= 2.5 * g_lo, (
        "replica scaling floor missed: %d good at N=%d vs %d at N=%d "
        "(< 2.5x)" % (g_hi, max(SCALING_LADDER), g_lo, min(SCALING_LADDER)))


def _smoke_ladder_holds(legs):
    for leg in legs.values():
        pc = leg["per_class"]
        gi = pc["interactive"]["goodput"] or 0.0
        gb = pc["best_effort"]["goodput"] or 0.0
        if not gi > gb:
            return False
    return True


def _assert_smoke(report):
    for name, leg in report["legs"].items():
        pc, ov = leg["per_class"], leg["overall"]
        # (no hangs) every admitted request reached a terminal outcome
        assert ov["unresolved"] == 0, (name, ov)
        resolved = sum(pc[c]["attempted"] for c in pc)
        gen = ov.get("generate")
        if gen is not None:
            # the mixed leg: every generation ALSO reached a terminal
            # outcome (admitted ones completed or failed typed — the
            # durable-decode no-hang contract), some really decoded,
            # and the predict ladder below still holds under the mix
            assert gen["unresolved"] == 0, (name, gen)
            assert gen["attempted"] > 0 and gen["ok"] > 0, (name, gen)
            resolved += gen["attempted"]
        assert resolved == ov["requests"], (name, resolved, ov)
        # the offered load really was overload: something got shed or
        # expired (otherwise the leg proves nothing about SLO behavior)
        shed = sum(pc[c][k] for c in pc
                   for k in ("shed_admission", "shed_queue_full",
                             "shed_degraded", "expired"))
        assert shed > 0, ("no overload pressure in leg %s: %s" % (name, pc))
        # the priority ladder: interactive strictly beats best_effort on
        # goodput-under-deadline, and interactive traffic mostly succeeds
        gi = pc["interactive"]["goodput"]
        gb = pc["best_effort"]["goodput"]
        assert gi is not None and gb is not None and gi > gb, (
            "priority ladder inverted in %s: interactive %.3f <= "
            "best_effort %.3f" % (name, gi or -1, gb or -1))
        assert gi >= 0.5, ("interactive goodput %.3f < 0.5 in %s"
                           % (gi, name))
    faulty = [leg for name, leg in report["legs"].items()
              if name.endswith("_faulty")]
    assert faulty and all(leg["overall"]["retries"] > 0 for leg in faulty), (
        "faulty legs recorded no retries")


def _ensure_host_devices(n):
    """Force >= ``n`` virtual CPU devices for the replica legs.  Only
    effective BEFORE jax's backend initializes — env-only here; when jax
    is already imported (in-process callers) the caller's mesh rules."""
    if "jax" in sys.modules:
        return
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    os.environ["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=%d" % n]).strip()


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true",
                        help="quick deterministic pass + SLO assertions")
    parser.add_argument("--process", choices=["poisson", "bursty"],
                        default=None, help="run only one arrival process")
    parser.add_argument("--overload", type=float, default=None,
                        help="offered rate as a multiple of capacity "
                             "(default 3; 4 for --scaling, so the top "
                             "rung is at its aggregate capacity while "
                             "the bottom rung is 4x overloaded)")
    parser.add_argument("--requests", type=int, default=None,
                        help="arrivals per leg")
    parser.add_argument("--replicas", type=int, default=1,
                        help="serve the legs from a ReplicaPool of N "
                             "device-pinned replicas (1 = single engine)")
    parser.add_argument("--decode", action="store_true",
                        help="add a mixed predict+generate leg per "
                             "arrival process: every %dth arrival rides "
                             "the pool's decode schedulers" % DECODE_EVERY)
    parser.add_argument("--session-mix", type=int, nargs="?", const=8,
                        default=0, metavar="K",
                        help="conversational decode arrivals: cycle the "
                             "generate traffic over K live sessions "
                             "(default 8) with fixed per-session "
                             "prompts — session pins + sticky affinity "
                             "on the pool (implies --decode)")
    parser.add_argument("--scaling", action="store_true",
                        help="replica-scaling ladder: one warm pool, "
                             "rotation resized %s, fixed offered rate"
                             % (SCALING_LADDER,))
    parser.add_argument("--multi-model", action="store_true",
                        help="serving-plane leg: a ModelRouter over two "
                             "deployments, skewed Poisson traffic, "
                             "per-tenant quotas, and a mid-run cold "
                             "activation")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    if "JAX_PLATFORMS" not in os.environ and "JAX_PLATFORM_NAME" not in os.environ:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if args.scaling or args.multi_model or args.replicas > 1:
        _ensure_host_devices(max(max(SCALING_LADDER), args.replicas))

    results = {"mode": "smoke" if args.smoke else "full"}
    if args.scaling:
        n = args.requests or (1600 if args.smoke else 3200)
        results["scaling"] = run_scaling_bench(
            args.smoke, args.overload or 4.0, n, args.seed)
    elif args.multi_model:
        n = args.requests or (900 if args.smoke else 3600)
        results["multi_model"] = run_multi_model_bench(
            args.smoke, args.overload or 2.0, n, args.seed)
    else:
        n = args.requests or (600 if args.smoke else 2400)
        results["load"] = run_load_bench(args.smoke, args.process,
                                         args.overload or 3.0, n, args.seed,
                                         replicas=args.replicas,
                                         decode=args.decode
                                         or bool(args.session_mix),
                                         session_mix=args.session_mix)
    print(json.dumps(results, indent=2, sort_keys=True))
    return results


if __name__ == "__main__":
    main()
