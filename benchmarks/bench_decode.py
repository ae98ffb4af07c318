"""Decode-throughput benchmark: continuous batching vs per-sequence serving.

An OPEN-LOOP load (the "millions of users" shape — arrivals don't wait
for completions): generation requests with mixed prompt lengths arrive on
a fixed schedule and each decodes ``max_new_tokens`` greedily.  Two legs
over the SAME decode model and the SAME compiled shapes:

  naive      : ``max_active=1`` — one sequence decodes at a time, the
               rest wait in the admission queue.  This is request-level
               scheduling, what a per-sequence serving loop gets.
  continuous : ``max_active=num_slots`` — iteration-level scheduling
               (Orca-style): new sequences are admitted into free decode
               slots *between* steps, so one fixed-shape decode dispatch
               serves up to ``num_slots`` sequences' next tokens at once
               over the paged KV cache.

Reported per leg: generated tokens/s, p50/p95 inter-token latency (gaps
between a sequence's consecutive token timestamps), p50/p95 time to
first token (enqueue -> first sampled token — the requeue-latency metric
open-loop load exposes), and the ``executor.compile_count()`` delta
across the serving window (must be 0: both legs replay warmed
executables).  Smoke mode (the CI gate via tools/check_decode.py)
asserts >= 2x tokens/s, bitwise per-sequence token equality between the
legs, and zero decode-step recompiles after warmup.

CPU-friendly by design: the win is scheduling arithmetic — how many
sequences' tokens ride one fixed-shape dispatch — the same lever on a
TPU, where the per-dispatch cost is even more expensive relative to
per-row compute (not measured on a chip).

Two further legs ride the same harness (ISSUE 15):

  --long-prompts   : a mixed long/short open-loop load through the SAME
                     continuous-batching config twice — monolithic
                     prefill vs chunked prefill
                     (``prefill_chunk_tokens``).  Monolithic prefill
                     head-of-line-blocks every active decode slot and
                     every queued short prompt for a long prompt's whole
                     prefill; chunking bounds the per-iteration prefill
                     work by the chunk budget.  A one-token-per-request
                     TTFT probe: reported per leg are p95 TTFT (overall
                     and over the SHORT prompts stuck behind the burst
                     — the interactive number chunking exists for) and
                     tokens/s; smoke asserts >= 3x better short-prompt
                     p95 TTFT at no tokens/s regression, plus bitwise
                     token equality between the legs.
  --repeated-prefix: a shared-prefix fan-out (one system prompt, many
                     tails) served with the prefix cache off vs on.
                     Reported: page hit rate and prefill-token
                     reduction; smoke asserts >= 50% fewer prompt
                     tokens prefilled and bitwise-identical outputs
                     warm vs cold.
  --multi-turn     : the conversational leg (ISSUE 20): K users x M
                     turns, each turn's prompt the user's FULL history
                     plus one utterance, served by a 3-replica
                     session-enabled ReplicaPool (session pins +
                     sticky affinity) vs a session-less pool fed the
                     identical full-history prompts.  Reported:
                     pool-wide prefill-token reduction, sticky-affinity
                     hits, pinned pages; smoke asserts >= 50% fewer
                     prefill tokens and bitwise warm == cold per turn.

Usage:
  python benchmarks/bench_decode.py            # full run, prints JSON
  python benchmarks/bench_decode.py --smoke    # quick run + assertions
  python benchmarks/bench_decode.py --long-prompts [--smoke]
  python benchmarks/bench_decode.py --repeated-prefix [--smoke]
  python benchmarks/bench_decode.py --multi-turn [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

VOCAB = 128


def build_model():
    from paddle_tpu.models import transformer as T

    params, meta = T.lm_params(seed=23, vocab_size=VOCAB, n_layer=2,
                               n_head=4, d_model=64, d_inner=128,
                               max_length=256)
    return T.build_decode_model(params, meta)


def make_load(n_requests, interarrival_s, max_new, seed=0):
    """Mixed-length prompts + an open-loop arrival schedule (uniform
    spacing with deterministic jitter, so runs are reproducible)."""
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(1, VOCAB, size=rng.randint(4, 28))
               .astype(np.int32) for _ in range(n_requests)]
    jitter = rng.uniform(0.0, interarrival_s * 0.5, size=n_requests)
    arrivals = np.arange(n_requests) * interarrival_s + jitter
    return prompts, arrivals, max_new


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else None


def run_leg(model, prompts, arrivals, max_new, max_active, num_slots,
            page_size, max_seq_len, **cfg_kw):
    from paddle_tpu import serving
    from paddle_tpu.executor import compile_count

    sched = serving.DecodeScheduler(model, serving.DecodeConfig(
        num_slots=num_slots, max_active=max_active, page_size=page_size,
        max_seq_len=max_seq_len, max_new_tokens=max_new,
        queue_capacity=max(256, 2 * len(prompts)), **cfg_kw))
    c0 = compile_count()
    t0 = time.perf_counter()
    futs = []
    for p, at in zip(prompts, arrivals):
        delay = (t0 + at) - time.perf_counter()
        if delay > 0:
            time.sleep(delay)  # open loop: the schedule, not completions
        futs.append(sched.submit(p, max_new_tokens=max_new))
    outs = [f.result(timeout=600) for f in futs]
    elapsed = time.perf_counter() - t0
    compiles = compile_count() - c0
    itl, ttft = [], []
    for f in futs:
        stamps = f.token_times
        ttft.append(stamps[0] - f.enqueue_ts)
        itl.extend(b - a for a, b in zip(stamps, stamps[1:]))
    n_tokens = sum(len(o) for o in outs)
    sched.stop()
    return {
        "max_active": max_active,
        "requests": len(prompts),
        "generated_tokens": n_tokens,
        "elapsed_s": round(elapsed, 4),
        "tokens_per_s": round(n_tokens / elapsed, 1),
        "p50_inter_token_ms": round(_pct(itl, 50) * 1e3, 3) if itl else None,
        "p95_inter_token_ms": round(_pct(itl, 95) * 1e3, 3) if itl else None,
        "p50_ttft_ms": round(_pct(ttft, 50) * 1e3, 3),
        "p95_ttft_ms": round(_pct(ttft, 95) * 1e3, 3),
        "compiles_during_serve": int(compiles),
    }, outs, ttft


def build_long_model(d_model=64, d_inner=128, max_length=256):
    """A decode model whose geometry admits LONG prompts — the workload
    where monolithic prefill's head-of-line block is visible.  The
    --long-prompts leg sizes it up (d_model 256, T 512) so prefill is
    COMPUTE-bound rather than dispatch-bound, as on a real chip."""
    from paddle_tpu.models import transformer as T

    params, meta = T.lm_params(seed=29, vocab_size=VOCAB, n_layer=2,
                               n_head=4, d_model=d_model, d_inner=d_inner,
                               max_length=max_length)
    return T.build_decode_model(params, meta)


def make_mixed_load(n_requests, interarrival_s, max_new, seed=1,
                    n_long=4, long_len=(448, 504), short_len=(4, 24)):
    """Mixed long/short open-loop load: ``n_long`` LONG prompts arrive
    FIRST in a burst, a queue of short interactive prompts right behind
    them — the canonical head-of-line-blocking shape (a batch job's
    context dump landing just before the interactive traffic).  Arrivals
    are open-loop (the schedule never waits for completions)."""
    rng = np.random.RandomState(seed)
    prompts = []
    for i in range(n_requests):
        lo, hi = long_len if i < n_long else short_len
        prompts.append(rng.randint(1, VOCAB, size=rng.randint(lo, hi))
                       .astype(np.int32))
    # longs land together at t~0; shorts trickle in behind them while
    # the long prefills are (monolithically) hogging the engine
    arrivals = np.concatenate([
        np.arange(n_long) * 2e-3,
        0.05 + np.arange(n_requests - n_long) * interarrival_s,
    ])
    return prompts, arrivals, max_new


def long_prompts_report(args):
    """Chunked vs monolithic prefill under a mixed long/short load —
    the decode-side head-of-line-blocking benchmark."""
    n_req = args.requests or (24 if args.smoke else 32)
    # a pure TTFT probe: one token per request, so the measurement is
    # prefill scheduling alone (decode-throughput neutrality is the
    # default --smoke leg's contract; chunked and monolithic share the
    # identical compiled decode step)
    max_new = args.max_new or 1
    inter = (args.interarrival_ms
             if args.interarrival_ms is not None else 12.0) / 1e3
    chunk = args.chunk_tokens or 256
    n_long = max(1, n_req // 3)
    model = build_long_model(d_model=256, d_inner=512, max_length=512)
    prompts, arrivals, max_new = make_mixed_load(
        n_req, inter, max_new, n_long=n_long)
    legs, outs = {}, {}
    for name, kw in (("monolithic", {}),
                     ("chunked", {"prefill_chunk_tokens": chunk})):
        legs[name], outs[name], ttft_raw = run_leg(
            model, prompts, arrivals, max_new, args.long_slots,
            args.long_slots, page_size=16, max_seq_len=512, **kw)
        # the interactive-latency number this leg exists for: TTFT of
        # the SHORT prompts stuck behind the long burst (chunked prefill
        # deliberately trades long-prompt TTFT for it, vLLM-style)
        legs[name]["p95_short_ttft_ms"] = round(
            _pct([ttft_raw[i] for i in range(n_req)
                  if len(prompts[i]) < 100], 95) * 1e3, 3)
    bitwise = all(a.tobytes() == b.tobytes()
                  for a, b in zip(outs["monolithic"], outs["chunked"]))
    ttft_gain = (legs["monolithic"]["p95_short_ttft_ms"]
                 / legs["chunked"]["p95_short_ttft_ms"])
    tps_ratio = (legs["chunked"]["tokens_per_s"]
                 / legs["monolithic"]["tokens_per_s"])
    report = {"decode_long_prompts": {
        "workload": {
            "requests": n_req, "long_prompts": n_long,
            "max_new_tokens": max_new, "interarrival_ms": inter * 1e3,
            "num_slots": args.long_slots, "prefill_chunk_tokens": chunk,
            "open_loop": True,
        },
        "monolithic": legs["monolithic"],
        "chunked": legs["chunked"],
        "p95_short_ttft_gain": round(ttft_gain, 2),
        "tokens_per_s_ratio": round(tps_ratio, 3),
        "bitwise_equal": bool(bitwise),
    }}
    print(json.dumps(report, indent=2))
    if args.smoke:
        assert bitwise, "chunked prefill changed some sequence's tokens"
        assert legs["chunked"]["compiles_during_serve"] == 0, (
            "chunked leg served with a recompile: %r" % legs["chunked"])
        assert ttft_gain >= 3.0, (
            "chunked prefill short-prompt p95 TTFT gain %.2fx < 3x"
            % ttft_gain)
        # "no tokens/s regression": equal total work, different slicing —
        # leave a 10%% floor for shared-CI scheduling noise
        assert tps_ratio >= 0.9, (
            "chunked prefill cost %.1f%% tokens/s" % ((1 - tps_ratio) * 100))
    return 0


def repeated_prefix_report(args):
    """Prefix cache off vs on over a shared-prefix fan-out (one system
    prompt, many tails) — the recomputation-avoided benchmark."""
    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.executor import compile_count

    n_req = args.requests or (10 if args.smoke else 32)
    max_new = args.max_new or (8 if args.smoke else 16)
    rng = np.random.RandomState(5)
    prefix = rng.randint(1, VOCAB, size=112).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.randint(1, VOCAB, size=8)
                               .astype(np.int32)])
               for _ in range(n_req)]
    model = build_long_model()
    prefill_tokens = obs.counter("serving.decode.prefill_tokens")
    hit_pages = obs.counter("serving.decode.kv_hit_pages")
    miss_pages = obs.counter("serving.decode.kv_miss_pages")
    legs, outs = {}, {}
    for name, kw in (("cold", {}), ("warm", {"prefix_cache": True})):
        sched = serving.DecodeScheduler(model, serving.DecodeConfig(
            num_slots=args.slots, page_size=16, max_seq_len=256,
            max_new_tokens=max_new, queue_capacity=max(256, 2 * n_req),
            **kw))
        c0 = compile_count()
        p0, h0, m0 = prefill_tokens.value, hit_pages.value, miss_pages.value
        t0 = time.perf_counter()
        # sequential: each request completes before the next is admitted,
        # so every fan-out request after the first sees the prefix cached
        outs[name] = [sched.generate(p, timeout=600) for p in prompts]
        elapsed = time.perf_counter() - t0
        hits, misses = hit_pages.value - h0, miss_pages.value - m0
        legs[name] = {
            "requests": n_req,
            "elapsed_s": round(elapsed, 4),
            "prefill_tokens": prefill_tokens.value - p0,
            "kv_hit_pages": hits,
            "kv_miss_pages": misses,
            "hit_rate": round(hits / (hits + misses), 3)
            if hits + misses else 0.0,
            "compiles_during_serve": compile_count() - c0,
        }
        sched.stop()
    bitwise = all(a.tobytes() == b.tobytes()
                  for a, b in zip(outs["cold"], outs["warm"]))
    reduction = 1.0 - (legs["warm"]["prefill_tokens"]
                       / legs["cold"]["prefill_tokens"])
    report = {"decode_repeated_prefix": {
        "workload": {
            "requests": n_req, "prefix_tokens": int(prefix.shape[0]),
            "tail_tokens": 8, "max_new_tokens": max_new,
            "num_slots": args.slots,
        },
        "cold": legs["cold"],
        "warm": legs["warm"],
        "prefill_token_reduction": round(reduction, 3),
        "bitwise_equal": bool(bitwise),
    }}
    print(json.dumps(report, indent=2))
    if args.smoke:
        assert bitwise, "prefix cache changed some sequence's tokens"
        assert legs["warm"]["compiles_during_serve"] == 0, (
            "warm leg served with a recompile: %r" % legs["warm"])
        assert reduction >= 0.5, (
            "prefix cache avoided only %.0f%% of prefill tokens"
            % (reduction * 100))
        assert legs["warm"]["hit_rate"] >= 0.5, legs["warm"]
    return 0


def _ensure_host_devices(n):
    """Force >= ``n`` virtual CPU devices for the pool legs — env-only,
    so it must run BEFORE jax's backend initializes."""
    if "jax" in sys.modules:
        return
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    os.environ["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=%d" % n]).strip()


def multi_turn_report(args):
    """Conversational sessions vs session-less re-prefill: K users hold
    M-turn conversations against a 3-replica pool.  Turn t's prompt is
    the user's whole history (turn t-1's prompt + its generated tokens)
    plus a fresh utterance — the bitwise contract makes warm and cold
    prompts IDENTICAL, so the only difference the session machinery may
    make is how much of each prompt is recomputed."""
    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.executor import compile_count

    n_users = args.requests or (4 if args.smoke else 8)
    n_turns = 4 if args.smoke else 6
    max_new = args.max_new or 8
    rng = np.random.RandomState(9)
    base = [rng.randint(1, VOCAB, size=24).astype(np.int32)
            for _ in range(n_users)]
    utts = [[rng.randint(1, VOCAB, size=16).astype(np.int32)
             for _ in range(n_turns - 1)] for _ in range(n_users)]

    model = build_model()
    prefill_tokens = obs.counter("serving.decode.prefill_tokens")
    sticky = obs.counter("serving.affinity.sticky")

    def _cfg(**kw):
        return serving.DecodeConfig(
            num_slots=2, page_size=8,
            max_seq_len=32 * (n_turns + 1), max_new_tokens=max_new,
            prefill_chunk_tokens=32, queue_capacity=256, **kw)

    legs = {}
    # warm leg drives the conversations (its outputs BUILD the
    # histories); the cold leg replays the identical full-history
    # prompts through a session-less pool
    pool = serving.ReplicaPool(None, replicas=3, decode_model=model,
                               decode_config=_cfg(prefix_cache=True),
                               supervisor_interval_s=0.05)
    c0 = compile_count()
    p0, s0 = prefill_tokens.value, sticky.value
    hists = [list(map(int, b)) for b in base]
    warm = [[] for _ in range(n_users)]
    t0 = time.perf_counter()
    for t in range(n_turns):
        if t > 0:
            for u in range(n_users):
                hists[u] = hists[u] + list(map(int, utts[u][t - 1]))
        futs = [pool.generate_async(np.asarray(hists[u], np.int32),
                                    max_new_tokens=max_new,
                                    session="user-%d" % u)
                for u in range(n_users)]
        for u, f in enumerate(futs):
            out = list(map(int, f.result(timeout=600)))
            warm[u].append(out)
            hists[u] = hists[u] + out
    legs["warm"] = {
        "elapsed_s": round(time.perf_counter() - t0, 4),
        "prefill_tokens": prefill_tokens.value - p0,
        "sticky_affinity_hits": sticky.value - s0,
        "pinned_pages": pool.sessions.stats()["pinned_pages"],
        "compiles_during_serve": compile_count() - c0,
    }
    pool.stop()

    cold_pool = serving.ReplicaPool(None, replicas=3, decode_model=model,
                                    decode_config=_cfg(),
                                    supervisor_interval_s=0.05)
    c0 = compile_count()
    p0 = prefill_tokens.value
    hists = [list(map(int, b)) for b in base]
    cold = [[] for _ in range(n_users)]
    t0 = time.perf_counter()
    for t in range(n_turns):
        if t > 0:
            for u in range(n_users):
                hists[u] = hists[u] + list(map(int, utts[u][t - 1]))
        futs = [cold_pool.generate_async(np.asarray(hists[u], np.int32),
                                         max_new_tokens=max_new)
                for u in range(n_users)]
        for u, f in enumerate(futs):
            out = list(map(int, f.result(timeout=600)))
            cold[u].append(out)
            hists[u] = hists[u] + out
    legs["cold"] = {
        "elapsed_s": round(time.perf_counter() - t0, 4),
        "prefill_tokens": prefill_tokens.value - p0,
        "compiles_during_serve": compile_count() - c0,
    }
    cold_pool.stop()

    bitwise = warm == cold
    reduction = 1.0 - (legs["warm"]["prefill_tokens"]
                       / legs["cold"]["prefill_tokens"])
    report = {"decode_multi_turn": {
        "workload": {
            "users": n_users, "turns": n_turns,
            "base_prompt_tokens": 24, "utterance_tokens": 16,
            "max_new_tokens": max_new, "replicas": 3,
        },
        "warm": legs["warm"],
        "cold": legs["cold"],
        "prefill_token_reduction": round(reduction, 3),
        "bitwise_equal": bool(bitwise),
    }}
    print(json.dumps(report, indent=2))
    if args.smoke:
        assert bitwise, "sessions changed some turn's tokens"
        assert legs["warm"]["compiles_during_serve"] == 0, (
            "warm leg served with a recompile: %r" % legs["warm"])
        assert reduction >= 0.5, (
            "sessions avoided only %.0f%% of prefill tokens"
            % (reduction * 100))
        assert legs["warm"]["sticky_affinity_hits"] > 0, legs["warm"]
    return 0


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke", action="store_true",
                        help="small load + assertions (the CI gate)")
    parser.add_argument("--long-prompts", action="store_true",
                        help="mixed long/short leg: chunked vs "
                             "monolithic prefill (p95 TTFT, tokens/s)")
    parser.add_argument("--repeated-prefix", action="store_true",
                        help="shared-prefix leg: prefix cache hit rate "
                             "+ prefill-token reduction")
    parser.add_argument("--multi-turn", action="store_true",
                        help="conversational leg: session pins + sticky "
                             "affinity vs session-less full-history "
                             "re-prefill over a 3-replica pool")
    parser.add_argument("--requests", type=int, default=None)
    parser.add_argument("--max-new", type=int, default=None)
    parser.add_argument("--interarrival-ms", type=float, default=None)
    parser.add_argument("--slots", type=int, default=8)
    parser.add_argument("--long-slots", type=int, default=12,
                        help="num_slots for --long-prompts (> its long burst)")
    parser.add_argument("--chunk-tokens", type=int, default=None,
                        help="prefill chunk budget for --long-prompts")
    args = parser.parse_args(argv)

    if args.multi_turn:
        if "JAX_PLATFORMS" not in os.environ \
                and "JAX_PLATFORM_NAME" not in os.environ:
            os.environ["JAX_PLATFORMS"] = "cpu"
        _ensure_host_devices(4)
        return multi_turn_report(args)
    if args.long_prompts:
        return long_prompts_report(args)
    if args.repeated_prefix:
        return repeated_prefix_report(args)

    n_req = args.requests or (24 if args.smoke else 64)
    max_new = args.max_new or (16 if args.smoke else 32)
    inter = (args.interarrival_ms
             if args.interarrival_ms is not None
             else (2.0 if args.smoke else 4.0)) / 1e3

    model = build_model()
    prompts, arrivals, max_new = make_load(n_req, inter, max_new)
    legs = {}
    outs = {}
    # naive first: its backlog is the worst case, warm jax only once per
    # leg config (both legs share shapes, so the second leg is pre-warmed
    # at the jax level but still pays its own scheduler warmup)
    for name, active in (("naive", 1), ("continuous", args.slots)):
        legs[name], outs[name], _ = run_leg(
            model, prompts, arrivals, max_new, active, args.slots,
            page_size=16, max_seq_len=256)
    bitwise = all(a.tobytes() == b.tobytes()
                  for a, b in zip(outs["naive"], outs["continuous"]))
    speedup = (legs["continuous"]["tokens_per_s"]
               / legs["naive"]["tokens_per_s"])
    report = {"decode": {
        "workload": {
            "requests": n_req, "max_new_tokens": max_new,
            "interarrival_ms": inter * 1e3, "num_slots": args.slots,
            "vocab": VOCAB, "open_loop": True,
        },
        "naive": legs["naive"],
        "continuous": legs["continuous"],
        "continuous_batching_speedup": round(speedup, 2),
        "bitwise_equal": bool(bitwise),
    }}
    print(json.dumps(report, indent=2))
    if args.smoke:
        assert bitwise, "continuous batching changed some sequence's tokens"
        assert legs["continuous"]["compiles_during_serve"] == 0, (
            "decode served with a recompile: %r" % legs["continuous"])
        assert speedup >= 2.0, (
            "continuous batching speedup %.2fx < 2x" % speedup)
    return 0


if __name__ == "__main__":
    sys.exit(main())
