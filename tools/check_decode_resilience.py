#!/usr/bin/env python
"""CI gate for durable decode (ISSUE 17): pool-routed generation with
deterministic replay-on-failure and KV integrity guards, driven on a
4-replica forced-host-device pool on CPU.

Scenario 1 — kill one replica of four mid-decode (the tentpole):
  a mixed greedy + seeded burst runs fault-free (baseline), then again
  with kill_replica_mid_decode murdering replica 1's decode worker once
  it provably holds in-flight KV.  EVERY sequence — in flight on the
  dead replica, in flight on siblings, still queued — completes with
  tokens bitwise-identical to the fault-free run (journal replay +
  absolute-position PRNG folding), replays count on
  ``serving.decode.replays``, the supervisor revives the replica and it
  PROVABLY claims work again (exclusive-gate probe), zero recompiles
  during the baseline's steady-state serve, zero leaked KV pages after
  drain in both runs.

Scenario 2 — KV corruption isolation:
  with ``kv_guard=True`` + prefix caching, corrupt_kv_page poisons a
  page one decoding sequence privately owns.  Exactly that sequence
  fails typed (``KVCorruption``), its pages are scrubbed (pools finite
  again), and co-resident + prefix-sharing sequences finish
  bitwise-identical to a clean run — the shared prefix pages survive.

Scenario 3 — transient decode-step retry:
  flaky_execute fires transient faults at the decode-step dispatch;
  the step retries in place (``serving.decode.step_retries`` advances)
  and the output stays bitwise-identical.  A FATAL decode fault fails
  the sequence typed, un-retried.

Scenario 4 — cancellation:
  ``GenerateRequest.cancel()`` retires an active sequence at the next
  iteration boundary and drops a queued one at its admission touch —
  both fail ``ServingCancelled``, ``serving.decode.cancelled`` counts
  them, no pages leak.

Scenario 5 — replay budget:
  with ``replay_budget=0`` the killed replica's in-flight sequences
  fail typed (``ServingDegraded`` naming the budget) instead of
  replaying; everything else completes.

Scenario 6 — reset_pools live-sequence guard:
  ``PagedKVCache.reset_pools()`` under live sequences raises a typed
  ``ServingError`` listing the active seq ids; ``force=True`` (the
  recovery path) zeroes anyway.

Runnable locally:
    python tools/check_decode_resilience.py
and wired into the tier-1 flow via
tests/unittests/test_decode_resilience_gate.py.

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
# the virtual device mesh MUST be forced before jax's backend initializes
_flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
          if "xla_force_host_platform_device_count" not in f]
os.environ["XLA_FLAGS"] = " ".join(
    _flags + ["--xla_force_host_platform_device_count=4"]).strip()

import numpy as np  # noqa: E402

KILLED = 1          # replica index scenario 1/5 murder


@functools.lru_cache(maxsize=None)
def _model(eos_id=None):
    """One model object for the gate: every pool's replicas dispatch its step
    programs (``DecodeModel.step_programs``), so a shape is traced once and a
    device compiles it once, whichever scenario got there first."""
    from paddle_tpu.models import transformer as T

    params, meta = T.lm_params(seed=31, vocab_size=60, n_layer=2,
                               n_head=2, d_model=32, d_inner=64,
                               max_length=128)
    return T.build_decode_model(params, meta, eos_id=eos_id)


def _cfg(**kw):
    from paddle_tpu import serving

    base = dict(num_slots=2, page_size=8, max_seq_len=64,
                max_new_tokens=16)
    base.update(kw)
    return serving.DecodeConfig(**base)


def _pool(model, replicas=4, **cfg_kw):
    from paddle_tpu import serving

    return serving.ReplicaPool(
        None, replicas=replicas, decode_model=model,
        decode_config=_cfg(**cfg_kw), supervisor_interval_s=0.05)


def _prompts(seed, n, lo=4, hi=16):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 60, size=rng.randint(lo, hi)).astype(np.int32)
            for _ in range(n)]


def _submit_burst(pool, prompts):
    """Mixed legs, one submission order: even indices greedy, odd
    seeded-sampling with the seed left to the POOL's admission pinning
    (the replay-determinism path under test)."""
    futs = []
    for i, p in enumerate(prompts):
        temp = 0.0 if i % 2 == 0 else 0.7
        futs.append(pool.generate_async(p, temperature=temp))
    return futs


def scenario_kill_replica_bitwise():
    from paddle_tpu import observability as obs
    from paddle_tpu.executor import compile_count
    from paddle_tpu.testing import faults

    model = _model()
    prompts = _prompts(0, 12)   # 12 seqs > 8 pool slots: some queued
                                # behind the burst when the kill lands

    # fault-free baseline + the steady-state zero-recompile assert
    pool = _pool(model)
    try:
        for f in _submit_burst(pool, _prompts(7, 8)):   # warm claim paths
            f.result(timeout=300)
        c0 = compile_count()
        base = [np.asarray(f.result(timeout=300))
                for f in _submit_burst(pool, prompts)]
        d = compile_count() - c0
        assert d == 0, "steady-state serve recompiled %d times" % d
        assert pool.drain_decode(timeout=30)
        leaked = [r.decoder._cache.used_pages for r in pool._replicas]
        assert not any(leaked), "baseline leaked KV pages: %s" % leaked
    finally:
        pool.stop()

    # the kill run: SAME warm-up + submission order (pool-level seed
    # pinning counts admissions, so the sequence of puts must match the
    # baseline for the seeded legs to compare), replica 1 dies mid-decode
    replays0 = obs.counter("serving.decode.replays").value or 0
    pool = _pool(model)
    try:
        for f in _submit_burst(pool, _prompts(7, 8)):
            f.result(timeout=300)
        with faults.kill_replica_mid_decode(KILLED, min_tokens=2) as fired:
            futs = _submit_burst(pool, prompts)
            outs = [np.asarray(f.result(timeout=300)) for f in futs]
        assert fired[0] == 1, "kill hook fired %d times" % fired[0]
        bad = [i for i in range(len(prompts))
               if base[i].tobytes() != outs[i].tobytes()]
        assert not bad, (
            "%d/%d sequences differ from the fault-free run after the "
            "replica kill (first: %d)" % (len(bad), len(prompts), bad[0]))
        replays = (obs.counter("serving.decode.replays").value or 0) \
            - replays0
        assert replays >= 1, "no replay counted on serving.decode.replays"

        # supervisor revival, provable re-claim: wait for the restart,
        # then open ONLY the revived replica's gate and make it serve
        rep = pool._replicas[KILLED]
        deadline = time.perf_counter() + 10
        while not rep.decoder.alive and time.perf_counter() < deadline:
            time.sleep(0.02)
        assert rep.decoder.alive, "supervisor never revived replica %d" \
            % KILLED
        before = rep.decoder.stats()["completed"]
        for r in pool._replicas:
            r.active = r.index == KILLED
        time.sleep(0.2)   # let siblings' in-flight queue.get()s (gate
        try:              # already passed) time out before probing
            probe = [pool.generate_async(p) for p in _prompts(9, 4)]
            for f in probe:
                f.result(timeout=300)
        finally:
            for r in pool._replicas:
                r.active = True
        claimed = rep.decoder.stats()["completed"] - before
        assert claimed == 4, (
            "revived replica completed %d/4 exclusive-gate probes"
            % claimed)
        assert pool.drain_decode(timeout=30)
        leaked = [r.decoder._cache.used_pages for r in pool._replicas]
        assert not any(leaked), "kill run leaked KV pages: %s" % leaked
    finally:
        pool.stop()
    return ("kill 1-of-4 mid-decode: %d seqs bitwise (greedy+seeded), "
            "%d replay(s), revived replica claimed 4/4, 0 recompiles, "
            "0 leaked pages OK" % (len(prompts), replays))


def scenario_corrupt_kv_isolation():
    from paddle_tpu import observability as obs
    from paddle_tpu import serving

    model = _model()
    prefix = np.arange(1, 17, dtype=np.int32)          # 2 full pages
    mk = lambda tail: np.concatenate(  # noqa: E731
        [prefix, np.asarray(tail, np.int32)])
    pa, pb, pc = mk([21, 22, 23]), mk([31, 32, 33]), mk([41, 42, 43])
    kw = dict(num_slots=4, prefill_chunk_tokens=8, prefix_cache=True,
              kv_guard=True)

    clean = serving.DecodeScheduler(model, _cfg(**kw))
    warm = clean.generate(pa)                # registers the prefix pages
    ca = clean.generate(pa)
    cb = clean.generate(pb)
    cc = clean.generate(pc)
    assert np.array_equal(warm, ca), "prefix-cache warm hit not bitwise"
    clean.stop()

    trips0 = obs.counter("serving.decode.kv_guard_trips").value or 0
    sched = serving.DecodeScheduler(model, _cfg(**kw))
    from paddle_tpu.testing import faults

    try:
        assert np.array_equal(np.asarray(sched.generate(pa)), ca)
        # B and C co-resident (and sharing A's registered prefix); B's
        # private tail page gets poisoned once it is decoding
        fb = sched.submit(pb)
        fc = sched.submit(pc)
        with faults.corrupt_kv_page(sched, seq=fb.seq, after_tokens=1) \
                as fired:
            try:
                fb.result(timeout=300)
                raise AssertionError(
                    "corrupted sequence completed instead of failing "
                    "KVCorruption")
            except serving.KVCorruption:
                pass
            out_c = np.asarray(fc.result(timeout=300))
        assert fired[0] == 1
        assert np.array_equal(out_c, cc), (
            "co-resident sequence's tokens changed under the neighbor's "
            "KV corruption")
        trips = (obs.counter("serving.decode.kv_guard_trips").value or 0) \
            - trips0
        assert trips == 1, "kv_guard_trips moved %d (want 1)" % trips
        # scrub proof: the pools are finite again, and the SHARED prefix
        # survived — a warm re-run of A and a fresh B both come back
        # bitwise against the clean scheduler
        import jax.numpy as jnp

        assert bool(jnp.isfinite(sched._cache.k_pool).all()), (
            "k_pool still holds non-finite values after the scrub")
        assert np.array_equal(np.asarray(sched.generate(pa)), ca)
        assert np.array_equal(np.asarray(sched.generate(pb)), cb)
        assert sched.stats()["kv_pages_used"] == 0
    finally:
        sched.stop()
    return ("corrupt_kv_page: owner failed KVCorruption, co-resident + "
            "prefix-sharing sequences bitwise-intact, pools scrubbed "
            "finite OK")


def scenario_decode_step_retry():
    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.testing import faults

    model = _model()
    prompt = np.arange(1, 9, dtype=np.int32)
    sched = serving.DecodeScheduler(model, _cfg())
    try:
        base = np.asarray(sched.generate(prompt, temperature=0.6, seed=5))
        # transient: fires only on dispatches carrying a request that
        # already accepted a token — i.e. the DECODE step, not prefill
        decoding = lambda rs: any(  # noqa: E731
            len(r.journal.accepted) >= 1 for r in rs
            if hasattr(r, "journal"))
        r0 = obs.counter("serving.decode.step_retries").value or 0
        with faults.flaky_execute(times=2, match=decoding) as fired:
            out = np.asarray(sched.generate(prompt, temperature=0.6,
                                            seed=5))
        retries = (obs.counter("serving.decode.step_retries").value or 0) \
            - r0
        assert fired[0] == 2 and retries == 2, (
            "fired %d faults, counted %d step retries (want 2/2)"
            % (fired[0], retries))
        assert np.array_equal(out, base), (
            "retried decode run not bitwise vs fault-free")
        # fatal: fails typed, un-retried
        r1 = obs.counter("serving.decode.step_retries").value or 0
        fatal = lambda rs: ValueError("injected fatal decode fault")  # noqa
        with faults.flaky_execute(times=1, match=decoding,
                                  exc_factory=fatal):
            try:
                sched.generate(prompt)
                raise AssertionError("fatal decode fault did not fail "
                                     "the sequence")
            except ValueError:
                pass
        assert (obs.counter("serving.decode.step_retries").value or 0) \
            == r1, "fatal decode fault was retried"
        assert sched.stats()["kv_pages_used"] == 0
    finally:
        sched.stop()
    return ("decode-step faults: 2 transients retried bitwise "
            "(step_retries +2), fatal failed typed un-retried OK")


def scenario_cancel():
    from paddle_tpu import observability as obs
    from paddle_tpu import serving

    model = _model()
    sched = serving.DecodeScheduler(
        model, _cfg(max_active=1, max_new_tokens=48))
    c0 = obs.counter("serving.decode.cancelled").value or 0
    try:
        prompt = np.arange(1, 9, dtype=np.int32)
        active = sched.submit(prompt)        # decoding (sole seat)
        queued = sched.submit(prompt)        # behind it in the queue
        while not active.token_times:
            time.sleep(0.002)
        assert active.cancel() and queued.cancel()
        for req, where in ((active, "active"), (queued, "queued")):
            try:
                req.result(timeout=60)
                raise AssertionError("%s request completed after "
                                     "cancel()" % where)
            except serving.ServingCancelled:
                pass
        assert not active.cancel(), "cancel() on a done request said True"
        # the runtime still serves, nothing leaked
        out = sched.generate(prompt, max_new_tokens=4)
        assert len(out) == 4
        assert sched.stats()["kv_pages_used"] == 0
        cancelled = (obs.counter("serving.decode.cancelled").value or 0) \
            - c0
        assert cancelled == 2, "cancelled counter moved %d (want 2)" \
            % cancelled
    finally:
        sched.stop()
    return ("cancel(): active seq retired at iteration boundary, queued "
            "dropped at admission, both ServingCancelled, 0 leaked "
            "pages OK")


def scenario_replay_budget():
    from paddle_tpu import serving
    from paddle_tpu.testing import faults

    model = _model()
    # 2 replicas suffice here — the 4-wide topology is scenario 1's job
    pool = _pool(model, replicas=2, replay_budget=0, max_new_tokens=16)
    try:
        with faults.kill_replica_mid_decode(KILLED, min_tokens=2):
            futs = [pool.generate_async(p) for p in _prompts(3, 8)]
            budget_failures, completed = 0, 0
            for f in futs:
                try:
                    f.result(timeout=300)
                    completed += 1
                except serving.ServingDegraded as e:
                    assert "replay budget" in str(e), e
                    budget_failures += 1
        assert budget_failures >= 1, (
            "kill with replay_budget=0 failed nothing typed")
        assert budget_failures + completed == 8
        assert pool.drain_decode(timeout=30)
    finally:
        pool.stop()
    return ("replay_budget=0: %d in-flight sequence(s) failed typed "
            "ServingDegraded, %d completed OK"
            % (budget_failures, completed))


def scenario_reset_pools_guard():
    from paddle_tpu import serving

    model = _model()
    sched = serving.DecodeScheduler(
        model, _cfg(max_active=1, max_new_tokens=48))
    try:
        req = sched.submit(np.arange(1, 9, dtype=np.int32))
        while not req.token_times:
            time.sleep(0.002)
        try:
            sched._cache.reset_pools()
            raise AssertionError(
                "reset_pools zeroed KV under a live sequence")
        except serving.ServingError as e:
            assert "live sequence" in str(e) and str(req.seq) in str(e), e
        req.cancel()
        try:
            req.result(timeout=60)
        except serving.ServingCancelled:
            pass
        sched._cache.reset_pools(force=True)   # recovery path still works
    finally:
        sched.stop()
    return ("reset_pools: refused typed under a live sequence (seq "
            "listed), force=True zeroed OK")


# every scenario of the gate, once: main() runs them in a row, and
# tests/unittests/test_*_gate.py makes each a case of its own
SCENARIOS = (
    scenario_kill_replica_bitwise,
    scenario_corrupt_kv_isolation,
    scenario_decode_step_retry,
    scenario_cancel,
    scenario_replay_budget,
    scenario_reset_pools_guard,
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
        sys.stderr.write("\ndecode resilience gate FAILED\n")
        return 1
    print("decode resilience gate OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
