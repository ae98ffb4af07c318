#!/usr/bin/env python
"""CI gate for the continuous-batching decode runtime: drive the real
DecodeScheduler / InferenceEngine.generate() on CPU and fail loudly on
any correctness, scheduling, or telemetry regression, so iteration-level
decode can't rot.

Scenario 1 — bitwise continuous-vs-per-sequence equality, no recompiles:
  mixed-length prompts through a continuously batched scheduler must
  come back bitwise-identical (token for token) to the same requests
  served one sequence at a time (max_active=1), with ZERO
  executor.compile_count() growth after warmup in either leg, and with
  the KV pool fully returned (free-on-retire) at the end.

Scenario 2 — admission contracts on the generate path:
  a full decode queue rejects with ServingQueueFull (and counts it), a
  queued request whose deadline passes is shed with ServingTimeout (and
  counts), live requests still answer, a stopped engine rejects with
  ServingClosed, and an EOS-capped sequence stops early.

Scenario 3 — serving.decode.* telemetry schema:
  a real generate run must populate the documented registry names
  (queue-depth/active-slot/KV gauges, request/token/prefill/step
  counters, prefill/decode/queue-wait timers), emit per-sequence spans,
  and stream decode_sequence records to record sinks.

Scenario 4 — what makes continuous batching fast, counted:
  the same backlog through ``max_active=1`` and through every slot:
  decode steps dispatched per generated token and the mean of live slots
  a step (``serving.decode.steps`` / ``.tokens``; tokens per step IS the
  mean of live slots).  The per-sequence loop pays a step a token; the
  batched one at most a step per ``num_slots / 2`` tokens.  Speed itself
  is the chip's (``chipbench/``), not a CPU wall clock's.

Scenario 5 — chunked prefill (ISSUE 15a):
  the same prompts through chunked (prefill_chunk_tokens) and monolithic
  prefill must return bitwise-identical tokens with ZERO recompiles
  after warmup and the KV pool fully returned, on BOTH attention
  engines (the CPU reference and the pallas kernel under interpret);
  a deadline that passes mid-prefill sheds between chunks with
  ServingTimeout, counts serving.decode.expired_mid_prefill, and
  reports time-in-queue vs time-in-prefill.

Scenario 6 — prefix cache (ISSUE 15b):
  a warm prefix cache must return bitwise-identical tokens to a cold
  one while prefilling >= 50% fewer prompt tokens on a shared-prefix
  workload (serving.decode.kv_hit_pages / prefill_tokens observable);
  refcounts return to zero after retirement (kv_pages_used == 0,
  kv_shared_pages == 0); and a pool too small to hold the working set
  still serves bitwise-correctly while evicting LRU refcount-zero
  pages (serving.decode.kv_evictions > 0).

Scenario 7 — head of line, counted in iterations:
  short prompts that arrive while a long prompt is mid-prefill get their
  first token within the short prompts' own chunks (the first in the
  iteration after it arrived), the long prompt still prefilling behind
  them; an iteration runs at most one
  chunk and one decode step (``serving.decode.iteration`` / ``.prefill``
  / ``.step`` spans in the order they closed); monolithic prefill serves
  the same tokens with the whole long prompt ahead of every short one.

Scenario 8 — repeated prefix, counted in tokens and pages:
  a shared-prefix fan-out, prefix cache off and on: prompt tokens
  prefilled (``serving.decode.prefill_tokens``), ``kv_hit_pages`` /
  ``kv_miss_pages``, zero recompiles, bitwise warm == cold.

Runnable locally:
    python tools/check_decode.py
and wired into the tier-1 flow via tests/unittests/test_decode_gate.py.

Exit code 0 = every scenario held.
"""
import functools
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

if "JAX_PLATFORMS" not in os.environ and "JAX_PLATFORM_NAME" not in os.environ:
    os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402


@functools.lru_cache(maxsize=None)
def _model(vocab=60, eos_id=None, attn_impl=None):
    """One model object a configuration: the scenarios' schedulers share its
    step programs (``DecodeModel.step_programs``)."""
    from paddle_tpu.models import transformer as T

    params, meta = T.lm_params(seed=31, vocab_size=vocab, n_layer=2,
                               n_head=2, d_model=32, d_inner=64,
                               max_length=128)
    return T.build_decode_model(params, meta, eos_id=eos_id,
                                attn_impl=attn_impl)


def _cfg(**kw):
    from paddle_tpu import serving

    base = dict(num_slots=4, page_size=8, max_seq_len=64,
                max_new_tokens=12)
    base.update(kw)
    return serving.DecodeConfig(**base)


def scenario_bitwise_and_no_recompile():
    from paddle_tpu import serving
    from paddle_tpu.executor import compile_count

    model = _model()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 60, size=rng.randint(2, 30)).astype(np.int32)
               for _ in range(14)]
    results = {}
    for name, active in (("continuous", 4), ("naive", 1)):
        sched = serving.DecodeScheduler(model, _cfg(max_active=active))
        c0 = compile_count()
        futs = [sched.submit(p) for p in prompts]
        results[name] = [f.result(timeout=300) for f in futs]
        d = compile_count() - c0
        assert d == 0, "%s leg recompiled %d times after warmup" % (name, d)
        st = sched.stats()
        assert st["kv_pages_used"] == 0, (
            "%s leg leaked %d KV pages" % (name, st["kv_pages_used"]))
        assert st["completed"] == len(prompts)
        sched.stop()
    bad = [i for i in range(len(prompts))
           if results["continuous"][i].tobytes()
           != results["naive"][i].tobytes()]
    assert not bad, (
        "%d/%d sequences differ continuous vs per-sequence (first: %d)"
        % (len(bad), len(prompts), bad[0]))
    return ("bitwise continuous == per-sequence: %d seqs, 0 recompiles, "
            "0 leaked pages OK" % len(prompts))


def scenario_admission_contracts():
    from paddle_tpu import observability as obs
    from paddle_tpu import serving

    model = _model()
    eng = serving.InferenceEngine(
        decode_model=model,
        decode_config=_cfg(queue_capacity=2, warmup=False),
        autostart=False)
    full0 = obs.counter("serving.decode.queue_full").value
    exp0 = obs.counter("serving.decode.expired").value
    live = eng.generate_async(np.array([3, 4, 5], np.int32),
                              max_new_tokens=2)
    doomed = eng.generate_async(np.array([3, 4, 5], np.int32),
                                max_new_tokens=2, deadline_ms=5)
    try:
        eng.generate_async(np.array([1], np.int32))
    except serving.ServingQueueFull:
        pass
    else:
        raise AssertionError("3rd request admitted past decode capacity 2")
    assert obs.counter("serving.decode.queue_full").value == full0 + 1
    time.sleep(0.05)  # the doomed request's deadline passes in queue
    eng.start()
    out = live.result(timeout=300)
    assert out.shape == (2,)
    try:
        doomed.result(timeout=300)
    except serving.ServingTimeout:
        pass
    else:
        raise AssertionError("expired generate request was still answered")
    assert obs.counter("serving.decode.expired").value == exp0 + 1
    eng.stop()
    try:
        eng.generate(np.array([1], np.int32))
    except serving.ServingClosed:
        pass
    else:
        raise AssertionError("stopped engine accepted a generate request")
    # EOS stops early: make the first greedily sampled token the EOS
    probe = serving.DecodeScheduler(_model(), _cfg())
    ref = probe.generate(np.array([5, 7], np.int32), max_new_tokens=8,
                         timeout=300)
    probe.stop()
    eos = int(ref[0])
    capped = serving.DecodeScheduler(_model(eos_id=eos), _cfg())
    out = capped.generate(np.array([5, 7], np.int32), max_new_tokens=8,
                          timeout=300)
    capped.stop()
    assert int(out[-1]) == eos and len(out) <= len(ref)
    return ("decode admission: queue-full rejected, expired shed, live "
            "answered, stopped closed, EOS stops early OK")


def scenario_telemetry_schema():
    from paddle_tpu import observability as obs
    from paddle_tpu import serving

    model = _model()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 60, size=rng.randint(2, 20)).astype(np.int32)
               for _ in range(8)]
    sink = obs.RingBufferSink(record_spans=True)
    obs.add_sink(sink)
    c0 = {n: obs.counter("serving.decode.%s" % n).value
          for n in ("requests", "tokens", "prefills", "steps", "retired")}
    try:
        sched = serving.DecodeScheduler(model, _cfg())
        futs = [sched.submit(p, max_new_tokens=6) for p in prompts]
        outs = [f.result(timeout=300) for f in futs]
        sched.stop()
    finally:
        obs.remove_sink(sink)
    d = {n: obs.counter("serving.decode.%s" % n).value - c0[n] for n in c0}
    assert d["requests"] == len(prompts) == d["prefills"] == d["retired"]
    n_tokens = sum(len(o) for o in outs)
    assert d["tokens"] == n_tokens, (d["tokens"], n_tokens)
    assert 0 < d["steps"] < n_tokens, (
        "steps %d not batched (tokens %d)" % (d["steps"], n_tokens))
    for cell in ("serving.decode.prefill", "serving.decode.step",
                 "serving.decode.queue_wait", "serving.decode.warmup"):
        stats = obs.histogram(cell).stats()
        assert stats and stats[0] > 0, "cell %s never observed" % cell
    for gname in ("serving.decode.queue_depth", "serving.decode.active_slots",
                  "serving.decode.kv_pages_used"):
        assert obs.gauge(gname).value == 0, "%s stuck nonzero" % gname
    assert obs.gauge("serving.decode.kv_pages_total").value > 0
    recs = [r for r in sink.records if r.get("type") == "decode_sequence"]
    assert len(recs) == len(prompts)
    for r in recs:
        for k in ("ts", "seq", "prompt_len", "generated", "shed",
                  "kv_pages_used", "queue_depth"):
            assert k in r, "decode_sequence record missing %r: %s" % (k, r)
    span_names = {s["name"] for s in sink.spans}
    assert {"serving.decode.sequence", "serving.decode.prefill",
            "serving.decode.step"} <= span_names, span_names
    return ("decode telemetry: %d seqs / %d tokens / %d steps, counters+"
            "timers+gauges+spans+records flowing OK"
            % (len(prompts), n_tokens, d["steps"]))


def scenario_batching_counts():
    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.executor import compile_count

    model = _model()
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, 60, size=rng.randint(4, 28)).astype(np.int32)
               for _ in range(16)]
    slots, new = 4, 12
    steps = obs.counter("serving.decode.steps")
    tokens = obs.counter("serving.decode.tokens")
    outs, per_step = {}, {}
    for name, active in (("naive", 1), ("continuous", slots)):
        # the whole backlog is queued before the loop starts, so what is
        # counted is the schedule and not the arrival times
        sched = serving.DecodeScheduler(
            model, _cfg(num_slots=slots, max_active=active,
                        max_new_tokens=new), autostart=False)
        c0, s0, t0 = compile_count(), steps.value, tokens.value
        futs = [sched.submit(p) for p in prompts]
        sched.start()
        outs[name] = [f.result(timeout=300).tobytes() for f in futs]
        sched.stop()
        assert compile_count() == c0, "%s leg recompiled" % name
        # a prompt's first token is its last chunk's; every other token
        # rode a decode step
        decoded = tokens.value - t0 - len(prompts)
        assert decoded == len(prompts) * (new - 1), decoded
        per_step[name] = decoded / float(steps.value - s0)
    assert outs["naive"] == outs["continuous"], (
        "continuous batching changed some sequence's tokens")
    # tokens per step is the mean of live slots a step: one for the
    # per-sequence loop (less what rode a step behind a retirement and
    # was dropped), at least half the slots for the batched one
    assert 0.9 <= per_step["naive"] <= 1.0, per_step
    assert per_step["continuous"] >= slots / 2.0, per_step
    return ("batching: %.2f -> %.2f tokens a decode step (mean live "
            "slots of %d), %.2f -> %.2f steps a token, bitwise, 0 "
            "recompiles OK"
            % (per_step["naive"], per_step["continuous"], slots,
               1 / per_step["naive"], 1 / per_step["continuous"]))


def scenario_chunked_prefill():
    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.executor import compile_count

    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 60, size=rng.randint(2, 50)).astype(np.int32)
               for _ in range(10)]
    # both attention engines: the CPU reference formulation and the TPU
    # pallas kernel run under interpret
    for impl in (None, "pallas"):
        model = _model(attn_impl=impl)
        n = len(prompts) if impl is None else 4
        results = {}
        for name, kw in (("monolithic", {}),
                         ("chunked", {"prefill_chunk_tokens": 8})):
            sched = serving.DecodeScheduler(model, _cfg(**kw))
            c0 = compile_count()
            futs = [sched.submit(p) for p in prompts[:n]]
            results[name] = [f.result(timeout=300) for f in futs]
            d = compile_count() - c0
            assert d == 0, ("%s/%s leg recompiled %d times after warmup"
                            % (name, impl, d))
            st = sched.stats()
            assert st["kv_pages_used"] == 0, (
                "%s leg leaked %d KV pages" % (name, st["kv_pages_used"]))
            sched.stop()
        bad = [i for i in range(n)
               if results["chunked"][i].tobytes()
               != results["monolithic"][i].tobytes()]
        assert not bad, (
            "%d/%d sequences differ chunked vs monolithic (impl=%s, "
            "first: %d)" % (len(bad), n, impl, bad[0]))
    # mid-prefill deadline shed: a doomed long prompt frees its budget
    # BETWEEN chunks, counts expired_mid_prefill, and its error reports
    # time-in-queue vs time-in-prefill
    from paddle_tpu.testing import faults

    model = _model()
    sched = serving.DecodeScheduler(
        model, _cfg(prefill_chunk_tokens=8), autostart=False)
    mid0 = obs.counter("serving.decode.expired_mid_prefill").value
    with faults.slow_execute(0.01):  # each chunk >= 10ms: 7 chunks > 30ms
        doomed = sched.submit(
            np.arange(1, 50, dtype=np.int32).repeat(2)[:50],
            max_new_tokens=8, deadline_ms=30)
        sched.start()
        # wait for the WORKER's shed (the future's own deadline check
        # races it and would win with a generic "unanswered" timeout)
        deadline = time.perf_counter() + 30
        while (obs.counter("serving.decode.expired_mid_prefill").value
               <= mid0 and time.perf_counter() < deadline):
            time.sleep(0.01)
        try:
            doomed.result(timeout=300)
        except serving.ServingTimeout as e:
            assert "mid-prefill" in str(e) and "in queue" in str(e), e
        else:
            raise AssertionError("mid-prefill deadline was not shed")
    assert obs.counter("serving.decode.expired_mid_prefill").value \
        == mid0 + 1
    st = sched.stats()
    assert st["kv_pages_used"] == 0, "mid-prefill shed leaked pages"
    # the scheduler still serves after the shed
    out = sched.generate(np.array([3, 4, 5], np.int32), max_new_tokens=2,
                         timeout=300)
    sched.stop()
    assert out.shape == (2,)
    return ("chunked prefill: bitwise == monolithic on both engines, 0 "
            "recompiles, 0 leaks, mid-prefill shed counted OK")


def scenario_prefix_cache():
    from paddle_tpu import observability as obs
    from paddle_tpu import serving

    model = _model()
    rng = np.random.RandomState(11)
    prefix = rng.randint(1, 60, size=32).astype(np.int32)
    prompts = [np.concatenate([prefix, rng.randint(1, 60, size=6)
                               .astype(np.int32)]) for _ in range(6)]
    prefill_tokens = obs.counter("serving.decode.prefill_tokens")
    hit_pages = obs.counter("serving.decode.kv_hit_pages")
    outs = {}
    for name, kw in (("cold", {}), ("warm", {"prefix_cache": True})):
        sched = serving.DecodeScheduler(model, _cfg(**kw))
        p0, h0 = prefill_tokens.value, hit_pages.value
        outs[name] = [sched.generate(p, timeout=300) for p in prompts]
        st = sched.stats()
        assert st["kv_pages_used"] == 0, (
            "%s leg left %d pages referenced after retirement"
            % (name, st["kv_pages_used"]))
        shared = obs.gauge("serving.decode.kv_shared_pages").value or 0
        assert shared == 0, (
            "%s leg left %d shared pages after retirement" % (name, shared))
        if name == "warm":
            warm_prefilled = prefill_tokens.value - p0
            warm_hits = hit_pages.value - h0
        else:
            cold_prefilled = prefill_tokens.value - p0
        sched.stop()
    bad = [i for i in range(len(prompts))
           if outs["warm"][i].tobytes() != outs["cold"][i].tobytes()]
    assert not bad, ("%d/%d sequences differ warm vs cold prefix cache"
                     % (len(bad), len(prompts)))
    assert warm_hits > 0, "shared-prefix workload produced no page hits"
    reduction = 1.0 - warm_prefilled / cold_prefilled
    assert reduction >= 0.5, (
        "prefix cache avoided only %.0f%% of prefill tokens (%d -> %d)"
        % (reduction * 100, cold_prefilled, warm_prefilled))
    # eviction under pressure: a pool too small for the distinct-prompt
    # working set must evict LRU refcount-zero pages and STILL serve
    # bitwise-correctly
    ev0 = obs.counter("serving.decode.kv_evictions").value
    distinct = [rng.randint(1, 60, size=40).astype(np.int32)
                for _ in range(6)]
    small = _cfg(prefix_cache=True, num_pages=13)  # 12 usable pages
    sched = serving.DecodeScheduler(model, small)
    got = [sched.generate(p, timeout=300) for p in distinct]
    assert sched.stats()["kv_pages_used"] == 0
    sched.stop()
    evictions = obs.counter("serving.decode.kv_evictions").value - ev0
    assert evictions > 0, (
        "undersized pool (12 pages, 6x6-page seqs) never evicted")
    ref = serving.DecodeScheduler(model, _cfg())
    want = [ref.generate(p, timeout=300) for p in distinct]
    ref.stop()
    bad = [i for i in range(len(distinct))
           if got[i].tobytes() != want[i].tobytes()]
    assert not bad, ("%d/%d sequences differ under eviction pressure"
                     % (len(bad), len(distinct)))
    return ("prefix cache: warm bitwise == cold with %.0f%% fewer "
            "prefill tokens (%d page hits), refcounts drained, %d "
            "evictions served correctly OK"
            % (reduction * 100, warm_hits, evictions))


class _SubmitBehind:
    """A span sink that submits ``prompts`` to ``sched`` when the first
    chunk of sequence ``seq`` closes: the arrivals land while that
    sequence is mid-prefill, whatever the machine's load."""
    wants_spans = True

    def __init__(self, sched, seq, prompts):
        self.sched, self.seq, self.prompts = sched, seq, prompts
        self.futures, self.spans = [], []

    def emit(self, record):
        pass

    def emit_span(self, name, ts, dur, thread, tags):
        self.spans.append((name, dict(tags or {})))
        if (name == "serving.decode.prefill" and not self.futures
                and tags.get("seq") == self.seq):
            self.futures = [self.sched.submit(p) for p in self.prompts]


def scenario_head_of_line_iterations():
    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.executor import compile_count

    model = _model()
    rng = np.random.RandomState(13)
    chunk = 8
    long_prompt = rng.randint(1, 60, size=100).astype(np.int32)
    shorts = [rng.randint(1, 60, size=n).astype(np.int32)
              for n in (5, 11, 7)]
    outs, ahead = {}, {}
    for name, kw in (("monolithic", {}),
                     ("chunked", {"prefill_chunk_tokens": chunk})):
        sched = serving.DecodeScheduler(
            model, _cfg(max_seq_len=128, max_new_tokens=6, **kw),
            autostart=False)
        c0 = compile_count()
        first = sched.submit(long_prompt)
        sink = _SubmitBehind(sched, first.seq, shorts)
        obs.add_sink(sink)
        try:
            sched.start()
            outs[name] = [first.result(timeout=300).tobytes()]
            outs[name] += [f.result(timeout=300).tobytes()
                           for f in sink.futures]
            sched.stop()
        finally:
            obs.remove_sink(sink)
        assert compile_count() == c0, "%s leg recompiled" % name
        assert len(sink.futures) == len(shorts)
        # the spans in the order they closed: a chunk and a step inside
        # the iteration that ran them, the iteration behind both
        turn, chunks, steps = 0, [], []
        for span, tags in sink.spans:
            if span == "serving.decode.iteration":
                turn += 1
            elif span == "serving.decode.prefill":
                chunks.append((turn, tags["seq"], tags["start"],
                               tags["rows"]))
            elif span == "serving.decode.step":
                steps.append(turn)
        assert len(set(t for t, _, _, _ in chunks)) == len(chunks), (
            "%s: two chunks in one iteration" % name)
        assert len(set(steps)) == len(steps), (
            "%s: two decode steps in one iteration" % name)
        arrived = chunks[0][0]         # the long prompt's first chunk
        long_done = max(t for t, seq, _, _ in chunks if seq == first.seq)
        ahead[name] = (arrived, long_done, chunks)
    assert outs["monolithic"] == outs["chunked"], (
        "chunked prefill changed some sequence's tokens")
    arrived, long_done, chunks = ahead["chunked"]
    # a chunk an iteration, whoever's: the long prompt's own and, ahead of
    # them (fewest chunks left first), the short prompts'
    n_chunks = sum(-(-len(p) // chunk) for p in [long_prompt] + shorts)
    assert long_done - arrived == n_chunks - 1 == len(chunks) - 1, (
        long_done, arrived, n_chunks, len(chunks))
    # a short prompt waits for the short prompts' chunks at most (its own
    # and, fewest chunks left first, the others'), never for the long one's
    short_chunks = n_chunks - -(-len(long_prompt) // chunk)
    waits = []
    for fut, prompt in zip(sink.futures, shorts):
        # its last chunk samples its first token
        last = max(t for t, seq, _, _ in chunks if seq == fut.seq)
        waits.append(last - arrived)
        assert last < long_done, (
            "a short prompt waited for the long prompt's whole prefill")
        assert waits[-1] <= short_chunks, (
            "a %d-token prompt got its first token %d iterations after it "
            "arrived; the short prompts have %d chunks between them"
            % (len(prompt), waits[-1], short_chunks))
    assert min(waits) == 1, waits      # the iteration after it arrived
    # monolithic: the long prompt is ONE chunk, and it is whole before
    # any short prompt has a token
    arrived, long_done, chunks = ahead["monolithic"]
    assert arrived == long_done and chunks[0][3] == len(long_prompt)
    return ("head-of-line: %d short prompts behind a %d-token prefill "
            "got their first token %s iterations after they arrived, "
            "the long prompt's last chunk %d after; one chunk and one "
            "step an iteration; bitwise == monolithic OK"
            % (len(shorts), len(long_prompt), waits, n_chunks - 1))


def scenario_repeated_prefix_counts():
    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.executor import compile_count

    model = _model()
    rng = np.random.RandomState(5)
    page, n_req, tail = 8, 10, 8
    prefix = rng.randint(1, 60, size=96).astype(np.int32)
    prompts = [np.concatenate([prefix, rng.randint(1, 60, size=tail)
                               .astype(np.int32)]) for _ in range(n_req)]
    cells = {n: obs.counter("serving.decode." + n)
             for n in ("prefill_tokens", "kv_hit_pages", "kv_miss_pages")}
    legs, outs = {}, {}
    for name, kw in (("cold", {}), ("warm", {"prefix_cache": True})):
        sched = serving.DecodeScheduler(
            model, _cfg(max_seq_len=128, max_new_tokens=8, **kw))
        c0 = compile_count()
        v0 = {n: c.value for n, c in cells.items()}
        # sequential: each request completes before the next is admitted,
        # so every fan-out request after the first sees the prefix cached
        outs[name] = [sched.generate(p, timeout=300).tobytes()
                      for p in prompts]
        sched.stop()
        legs[name] = {n: c.value - v0[n] for n, c in cells.items()}
        assert compile_count() == c0, "%s leg recompiled" % name
    assert outs["cold"] == outs["warm"], (
        "prefix cache changed some sequence's tokens")
    whole = len(prefix) + tail
    shared = len(prefix) // page
    assert legs["cold"] == {"prefill_tokens": n_req * whole,
                            "kv_hit_pages": 0, "kv_miss_pages": 0}, legs
    # the first request fills the index; every one behind it maps the
    # prefix's full pages and prefills its own tail alone
    assert legs["warm"]["prefill_tokens"] == whole + (n_req - 1) * tail, legs
    assert legs["warm"]["kv_hit_pages"] == (n_req - 1) * shared, legs
    hits, misses = legs["warm"]["kv_hit_pages"], legs["warm"]["kv_miss_pages"]
    assert hits / float(hits + misses) >= 0.5, legs
    return ("repeated prefix: %d -> %d prefill tokens, %d page hits / %d "
            "misses, 0 recompiles, bitwise warm == cold OK"
            % (legs["cold"]["prefill_tokens"],
               legs["warm"]["prefill_tokens"], hits, misses))


# every scenario of the gate, once: main() runs them in a row, and
# tests/unittests/test_*_gate.py makes each a case of its own
SCENARIOS = (
    scenario_bitwise_and_no_recompile,
    scenario_admission_contracts,
    scenario_telemetry_schema,
    scenario_batching_counts,
    scenario_chunked_prefill,
    scenario_prefix_cache,
    scenario_head_of_line_iterations,
    scenario_repeated_prefix_counts,
)


def main():
    failures = []
    for scenario in SCENARIOS:
        try:
            msg = scenario()
        except AssertionError as e:
            failures.append("%s FAILED: %s" % (scenario.__name__, e))
        else:
            print(msg)
    if failures:
        for f in failures:
            sys.stderr.write(f + "\n")
        sys.stderr.write("\ndecode gate FAILED\n")
        return 1
    print("decode gate OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
