"""Continuous-batching decode runtime: paged KV cache, scheduler, engine
generate() — the unit half of the ISSUE 6 acceptance (the end-to-end
throughput/bitwise/no-recompile gate lives in test_decode_gate.py).
"""
import collections
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu import serving  # noqa: E402
from paddle_tpu.executor import compile_count  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402


@pytest.fixture(scope="module")
def decode_model():
    params, meta = T.lm_params(seed=7, vocab_size=50, n_layer=2, n_head=2,
                               d_model=32, d_inner=64, max_length=128)
    return T.build_decode_model(params, meta)


def _cfg(**kw):
    base = dict(num_slots=4, page_size=8, max_seq_len=64, max_new_tokens=8)
    base.update(kw)
    return serving.DecodeConfig(**base)


def _prompts(n, rng, lo=2, hi=24, vocab=50):
    return [rng.randint(1, vocab, size=rng.randint(lo, hi)).astype(np.int32)
            for _ in range(n)]


# -- paged KV cache ----------------------------------------------------------

class TestPagedKVCache:
    def test_alloc_free_accounting(self):
        c = serving.PagedKVCache(2, num_pages=9, page_size=4, num_heads=2,
                                 head_dim=8, max_seq_len=32)
        assert c.free_pages == 8 and c.used_pages == 0
        a = c.alloc(3)
        b = c.alloc(5)
        assert len(a) == 3 and len(b) == 5 and c.free_pages == 0
        assert 0 not in a and 0 not in b  # scratch page never handed out
        assert c.alloc(1) is None         # exhausted -> None, not raise
        c.free(a)
        assert c.free_pages == 3 and c.used_pages == 5
        assert sorted(c.alloc(3)) == sorted(a)  # recycled

    def test_pages_for_and_table_row(self):
        c = serving.PagedKVCache(1, num_pages=17, page_size=4, num_heads=2,
                                 head_dim=8, max_seq_len=32)
        assert c.pages_for(1) == 1 and c.pages_for(4) == 1
        assert c.pages_for(5) == 2 and c.pages_for(32) == 8
        assert c.max_pages_per_seq == 8
        row = c.table_row([3, 5])
        assert row.shape == (8,) and row.dtype == np.int32
        assert list(row[:2]) == [3, 5] and (row[2:] == 0).all()

    def test_occupancy_fragmentation_gauges(self):
        c = serving.PagedKVCache(1, num_pages=11, page_size=4, num_heads=2,
                                 head_dim=8, max_seq_len=16)
        assert obs.gauge("serving.decode.kv_pages_total").value == 10
        c.alloc(5)
        c.publish_gauges(live_tokens=12)  # 12 of 20 reserved slots written
        assert obs.gauge("serving.decode.kv_pages_used").value == 5
        assert obs.gauge("serving.decode.kv_occupancy").value == 0.5
        assert abs(obs.gauge("serving.decode.kv_fragmentation").value
                   - (1 - 12 / 20)) < 1e-9

    def test_write_token_kv_into_the_stored_shape(self):
        import jax.numpy as jnp

        c = serving.PagedKVCache(2, num_pages=5, page_size=4, num_heads=2,
                                 head_dim=4, max_seq_len=16)
        # stored shape: layers stacked, heads FOLDED head-major into the
        # last axis (head h = [..., h*D:(h+1)*D]); the page axis is axis 1
        assert c.k_pool.shape == c.v_pool.shape == c.pool_shape == (2, 5, 4, 8)
        assert c.pages_shape(3) == (2, 3, 4, 8)
        tok_k = jnp.asarray(np.random.RandomState(0).randn(2, 3, 2, 4)
                            .astype(np.float32))  # S=3 slots
        kp, vp = serving.write_token_kv(
            c.k_pool, c.v_pool, tok_k, tok_k * 2,
            jnp.asarray([1, 4, 0], np.int32), jnp.asarray([2, 0, 0],
                                                          np.int32))
        assert kp.shape == c.pool_shape
        np.testing.assert_array_equal(          # slot 0 -> page 1, offset 2
            np.asarray(kp)[:, 1, 2].reshape(2, 2, 4), np.asarray(tok_k)[:, 0])
        np.testing.assert_array_equal(          # head 1 of slot 1 = page 4
            np.asarray(vp)[:, 4, 0, 4:8], 2 * np.asarray(tok_k)[:, 1, 1])

    @pytest.mark.parametrize("op", ["reset", "scrub"])
    def test_pool_shape_survives_reset_and_scrub(self, op):
        import jax.numpy as jnp

        from paddle_tpu.parallel.flash_attention import paged_kv_finite

        c = serving.PagedKVCache(2, num_pages=5, page_size=4, num_heads=2,
                                 head_dim=4, max_seq_len=16)
        pages = c.alloc(2)
        c.k_pool = c.k_pool.at[1, pages[0], 3, 7].set(jnp.nan)
        sweep = jnp.asarray(pages + [0], jnp.int32)
        assert np.asarray(paged_kv_finite(c.k_pool, c.v_pool, sweep)
                          ).tolist() == [False, True, True]
        if op == "reset":
            c.reset_pools(force=True)
        else:
            c.scrub_pages(pages)
        assert c.k_pool.shape == c.v_pool.shape == c.pool_shape
        assert c.k_pool.dtype == c.dtype
        assert np.asarray(paged_kv_finite(c.k_pool, c.v_pool, sweep)).all()
        assert not np.asarray(c.k_pool).any()


# -- scheduler ---------------------------------------------------------------

class TestDecodeScheduler:
    def test_continuous_equals_naive_bitwise(self, decode_model):
        rng = np.random.RandomState(0)
        prompts = _prompts(10, rng)
        cb = serving.DecodeScheduler(decode_model, _cfg())
        futs = [cb.submit(p) for p in prompts]
        got = [f.result(timeout=120) for f in futs]
        cb.stop()
        naive = serving.DecodeScheduler(decode_model, _cfg(max_active=1))
        want = [naive.generate(p, timeout=120) for p in prompts]
        naive.stop()
        for i, (g, w) in enumerate(zip(got, want)):
            assert g.tobytes() == w.tobytes(), (
                "sequence %d differs CB vs per-sequence" % i)

    def test_no_recompiles_after_warmup(self, decode_model):
        sched = serving.DecodeScheduler(decode_model, _cfg())
        rng = np.random.RandomState(1)
        c0 = compile_count()
        futs = [sched.submit(p) for p in _prompts(8, rng)]
        for f in futs:
            f.result(timeout=120)
        assert compile_count() == c0, "decode served with a recompile"
        sched.stop()

    def test_admits_and_retires_between_iterations(self, decode_model):
        # more sequences than slots, mixed lengths: the active set must
        # turn over without ever exceeding num_slots
        sched = serving.DecodeScheduler(decode_model, _cfg(num_slots=2))
        rng = np.random.RandomState(2)
        futs = [sched.submit(p, max_new_tokens=int(m)) for p, m in zip(
            _prompts(7, rng), rng.randint(1, 9, size=7))]
        outs = [f.result(timeout=120) for f in futs]
        st = sched.stats()
        assert st["completed"] == 7 and st["active"] == 0
        assert st["kv_pages_used"] == 0  # free-on-retire returned all
        assert all(o.ndim == 1 for o in outs)
        sched.stop()

    def test_eos_stops_early(self):
        params, meta = T.lm_params(seed=7, vocab_size=50, n_layer=2,
                                   n_head=2, d_model=32, d_inner=64,
                                   max_length=128)
        free = T.build_decode_model(params, meta)
        ref = serving.DecodeScheduler(free, _cfg())
        tokens = ref.generate(np.arange(1, 6, dtype=np.int32),
                              max_new_tokens=16, timeout=120)
        ref.stop()
        assert len(tokens) > 1
        eos = int(tokens[0])  # greedy decode repeats; first token recurs
        capped = T.build_decode_model(params, meta, eos_id=eos)
        sched = serving.DecodeScheduler(capped, _cfg())
        out = sched.generate(np.arange(1, 6, dtype=np.int32),
                             max_new_tokens=16, timeout=120)
        sched.stop()
        assert int(out[-1]) == eos and len(out) <= len(tokens)
        assert eos not in out[:-1]

    def test_deadline_shed_in_queue_and_backpressure(self, decode_model):
        cfg = _cfg(queue_capacity=2, warmup=False)
        sched = serving.DecodeScheduler(decode_model, cfg, autostart=False)
        exp0 = obs.counter("serving.decode.expired").value
        full0 = obs.counter("serving.decode.queue_full").value
        live = sched.submit(np.array([1, 2, 3], np.int32), max_new_tokens=2)
        doomed = sched.submit(np.array([1, 2, 3], np.int32),
                              max_new_tokens=2, deadline_ms=5)
        with pytest.raises(serving.ServingQueueFull):
            sched.submit(np.array([1], np.int32))
        assert obs.counter("serving.decode.queue_full").value == full0 + 1
        time.sleep(0.05)  # the doomed deadline passes in queue
        sched.start()
        assert live.result(timeout=120).shape == (2,)
        with pytest.raises(serving.ServingTimeout):
            doomed.result(timeout=120)
        assert obs.counter("serving.decode.expired").value == exp0 + 1
        sched.stop()
        with pytest.raises(serving.ServingClosed):
            sched.submit(np.array([1], np.int32))

    def test_malformed_prompts(self, decode_model):
        sched = serving.DecodeScheduler(decode_model,
                                        _cfg(warmup=False), autostart=False)
        with pytest.raises(serving.ServingError, match="non-empty"):
            sched.submit(np.zeros((0,), np.int32))
        with pytest.raises(serving.ServingError, match="non-empty"):
            sched.submit(np.zeros((2, 2), np.int32))
        with pytest.raises(serving.ServingError, match="max_seq_len"):
            sched.submit(np.arange(40, dtype=np.int32), max_new_tokens=60)
        with pytest.raises(serving.ServingError, match="prefill bucket"):
            sched.submit(np.arange(65, dtype=np.int32))
        sched.stop()

    def test_oversized_reservation_fails_cleanly(self, decode_model):
        # a request larger than the whole (idle) pool must fail, not wedge
        cfg = _cfg(num_pages=4, warmup=False)  # 3 usable pages = 24 tokens
        sched = serving.DecodeScheduler(decode_model, cfg)
        req = sched.submit(np.arange(1, 24, dtype=np.int32),
                           max_new_tokens=8)  # needs 4 pages
        with pytest.raises(serving.ServingError, match="pages"):
            req.result(timeout=60)
        # and the scheduler still serves fitting requests afterwards
        assert sched.generate(np.array([1, 2], np.int32), max_new_tokens=2,
                              timeout=120).shape == (2,)
        sched.stop()

    def test_telemetry_schema(self, decode_model):
        sink = obs.RingBufferSink(record_spans=True)
        obs.add_sink(sink)
        try:
            c0 = {n: obs.counter("serving.decode.%s" % n).value
                  for n in ("requests", "tokens", "prefills", "steps",
                            "retired")}
            sched = serving.DecodeScheduler(decode_model, _cfg())
            rng = np.random.RandomState(3)
            futs = [sched.submit(p, max_new_tokens=4)
                    for p in _prompts(5, rng)]
            outs = [f.result(timeout=120) for f in futs]
            sched.stop()
        finally:
            obs.remove_sink(sink)
        d = {n: obs.counter("serving.decode.%s" % n).value - c0[n]
             for n in c0}
        assert d["requests"] == 5 and d["prefills"] == 5
        assert d["retired"] == 5
        assert d["tokens"] == sum(len(o) for o in outs) == 20
        assert d["steps"] >= 3  # batched steps, not one per token
        for cell in ("serving.decode.prefill", "serving.decode.step",
                     "serving.decode.queue_wait"):
            assert obs.histogram(cell).stats()[0] > 0, cell
        assert obs.gauge("serving.decode.active_slots").value == 0
        assert obs.gauge("serving.decode.queue_depth").value == 0
        recs = [r for r in sink.records if r.get("type") == "decode_sequence"]
        assert len(recs) == 5
        for r in recs:
            for key in ("seq", "prompt_len", "generated", "shed",
                        "kv_pages_used", "queue_depth"):
                assert key in r, r
        assert {s["name"] for s in sink.spans} >= {
            "serving.decode.sequence", "serving.decode.prefill",
            "serving.decode.step"}

    @pytest.mark.parametrize("prompt_len,new", [(3, 2), (8, 6), (21, 9)])
    def test_walked_and_table_pages_counters(self, decode_model, prompt_len,
                                             new):
        """What the slot-bounded decode walk visits against what the whole
        table spans, summed over the decode steps (PR 31): their ratio is
        the share of the old one-page-a-step walk that is left."""
        names = ("steps", "paged.walked_pages", "paged.table_pages")
        c0 = {n: obs.counter("serving.decode." + n).value for n in names}
        cfg = _cfg(max_new_tokens=new)
        sched = serving.DecodeScheduler(decode_model, cfg)
        sched.generate(np.arange(1, prompt_len + 1, dtype=np.int32),
                       timeout=120)
        sched.stop()
        d = {n: obs.counter("serving.decode." + n).value - c0[n]
             for n in names}
        # the first token comes out of prefill; step j sees the prompt, the
        # j tokens fed before it and the one it feeds
        assert d["steps"] == new - 1
        assert d["paged.walked_pages"] == sum(
            -(-(prompt_len + j + 1) // cfg.page_size) for j in range(new - 1))
        assert d["paged.table_pages"] == d["steps"] * cfg.num_slots * (
            cfg.max_seq_len // cfg.page_size)

    def test_stop_drain_false_fails_pending(self, decode_model):
        sched = serving.DecodeScheduler(decode_model,
                                        _cfg(warmup=False), autostart=False)
        reqs = [sched.submit(np.array([1, 2], np.int32)) for _ in range(3)]
        sched.stop(drain=False)
        for r in reqs:
            with pytest.raises(serving.ServingClosed):
                r.result(timeout=10)

    def test_no_thread_leak(self, decode_model):
        before = threading.active_count()
        for _ in range(3):
            sched = serving.DecodeScheduler(decode_model,
                                            _cfg(warmup=False))
            sched.generate(np.array([1, 2, 3], np.int32), max_new_tokens=2,
                           timeout=120)
            sched.stop()
        assert threading.active_count() <= before


# -- one decode step in flight (ISSUE 36) -------------------------------------

_PIPE = ("steps", "steps_overlapped", "tokens", "tokens_discarded")


def _pipe_counters():
    return {n: obs.counter("serving.decode." + n).value for n in _PIPE}


def _pipe_delta(c0):
    return {n: v - c0[n] for n, v in _pipe_counters().items()}


def _free_run(model, prompts, **kw):
    """Each prompt's tokens served ALONE (``max_active=1``: the naive loop,
    one sequence at a time, so nothing joins or leaves beside it)."""
    naive = serving.DecodeScheduler(model, _cfg(max_active=1))
    try:
        return [naive.generate(p, timeout=120, **kw) for p in prompts]
    finally:
        naive.stop()


@pytest.fixture(scope="module")
def lm():
    return T.lm_params(seed=7, vocab_size=50, n_layer=2, n_head=2,
                       d_model=32, d_inner=64, max_length=128)


class TestOneStepInFlight:
    @pytest.mark.parametrize("temperature", [0.0, 0.9])
    def test_tokens_equal_the_naive_loop_with_slots_joining_and_leaving(
            self, decode_model, temperature):
        # more sequences than slots and every length different: slots are
        # reseated mid-run, each beside neighbours at other positions
        rng = np.random.RandomState(11)
        prompts = _prompts(9, rng)
        news = [int(m) for m in rng.randint(1, 14, size=9)]
        kw = [dict(max_new_tokens=m, temperature=temperature, seed=100 + i)
              for i, m in enumerate(news)]
        c0 = _pipe_counters()
        sched = serving.DecodeScheduler(decode_model, _cfg(num_slots=3))
        futs = [sched.submit(p, **k) for p, k in zip(prompts, kw)]
        got = [f.result(timeout=120) for f in futs]
        sched.stop()
        d = _pipe_delta(c0)
        naive = serving.DecodeScheduler(decode_model, _cfg(max_active=1))
        want = [naive.generate(p, timeout=120, **k)
                for p, k in zip(prompts, kw)]
        naive.stop()
        for i, (g, w) in enumerate(zip(got, want)):
            assert g.tobytes() == w.tobytes(), i
            assert len(g) == news[i]
        # the pipeline was engaged while they were served, and with no EOS,
        # cancel or deadline no computed token was dropped
        assert d["steps_overlapped"] >= d["steps"] // 2 > 0
        assert d["tokens"] == sum(news) and d["tokens_discarded"] == 0

    def test_the_token_after_eos_is_never_served_journalled_or_counted(
            self, lm):
        params, meta = lm
        free = T.build_decode_model(params, meta)
        rng = np.random.RandomState(5)
        prompts = _prompts(3, rng)
        kw = dict(max_new_tokens=20, temperature=1.0, seed=3)
        runs = _free_run(free, prompts, **kw)
        # an EOS that the first prompt samples in a decode step, mid-run
        eos = next(int(t) for k, t in enumerate(runs[0])
                   if 2 <= k <= 15 and t not in runs[0][:k])
        want = [r[:list(r).index(eos) + 1] if eos in r else r for r in runs]
        # a slot that samples EOS in a decode step with room left rode the
        # step behind it: one token a slot, computed and dropped
        riders = sum(1 for w in want if w[-1] == eos and 1 < len(w) < 20)
        assert riders >= 1
        capped = T.build_decode_model(params, meta, eos_id=eos)
        c0 = _pipe_counters()
        sched = serving.DecodeScheduler(capped, _cfg())
        futs = [sched.submit(p, **kw) for p in prompts]
        got = [f.result(timeout=120) for f in futs]
        sched.stop()
        d = _pipe_delta(c0)
        for f, g, w in zip(futs, got, want):
            assert g.tobytes() == np.asarray(w, np.int32).tobytes()
            assert f.journal.tokens().tobytes() == g.tobytes()
            assert len(f.token_times) == len(g)
        assert d["tokens"] == sum(len(w) for w in want)
        assert d["tokens_discarded"] == riders
        st = sched.stats()
        assert st["kv_pages_used"] == 0 and st["active"] == 0

    @pytest.mark.parametrize("how", ["cancel", "deadline"])
    def test_a_cancel_or_a_deadline_with_a_step_in_flight(self, decode_model,
                                                          how):
        from paddle_tpu.testing import faults

        c0 = _pipe_counters()
        sched = serving.DecodeScheduler(decode_model, _cfg(max_new_tokens=56))
        prompt = np.arange(1, 7, dtype=np.int32)
        with faults.slow_execute(0.01):
            req = sched.submit(prompt,
                               deadline_ms=400 if how == "deadline" else None)
            if how == "cancel":
                while len(req.token_times) < 4:
                    time.sleep(0.002)
                assert req.cancel()
            with pytest.raises(serving.ServingCancelled if how == "cancel"
                               else serving.ServingTimeout):
                req.result(timeout=120)
            # result() gives up at the deadline by the CLIENT's clock; the
            # worker sheds the slot at its next iteration boundary
            while not req.done():
                time.sleep(0.002)
        served = len(req.journal.accepted)
        assert 0 < served < 56
        time.sleep(0.05)
        sched.stop()
        d = _pipe_delta(c0)
        # the step in flight when the request left was read and dropped: no
        # token reached the journal, the stamps or the counter after it
        assert len(req.journal.accepted) == len(req.token_times) == served
        assert d["tokens"] == served and d["tokens_discarded"] == 1
        assert sched.stats()["kv_pages_used"] == 0
        want = _free_run(decode_model, [prompt], max_new_tokens=56)[0]
        assert req.journal.tokens().tobytes() == want[:served].tobytes()

    def test_a_slot_index_reseated_between_dispatch_and_commit(self, lm):
        params, meta = lm
        free = T.build_decode_model(params, meta)
        first = np.arange(1, 6, dtype=np.int32)
        second = np.arange(9, 20, dtype=np.int32)
        kw = dict(max_new_tokens=20, temperature=1.0, seed=3)
        runs = _free_run(free, [first, second], **kw)
        eos = next(int(t) for k, t in enumerate(runs[0])
                   if 2 <= k <= 15 and t not in runs[0][:k]
                   and t not in runs[1])
        capped = T.build_decode_model(params, meta, eos_id=eos)
        c0 = _pipe_counters()
        # ONE slot: the second request takes index 0 while the step the
        # first one rode past its EOS is still unread
        sched = serving.DecodeScheduler(capped, _cfg(num_slots=1),
                                        autostart=False)
        futs = [sched.submit(first, **kw), sched.submit(second, **kw)]
        sched.start()
        got = [f.result(timeout=120) for f in futs]
        sched.stop()
        d = _pipe_delta(c0)
        k = list(runs[0]).index(eos)
        assert got[0].tobytes() == runs[0][:k + 1].tobytes()
        assert got[1].tobytes() == runs[1].tobytes()
        assert futs[1].journal.tokens().tobytes() == runs[1].tobytes()
        assert d["tokens_discarded"] == 1
        assert d["tokens"] == k + 1 + 20

    @pytest.mark.parametrize("kv_guard", [False, True])
    def test_share_of_steps_overlapped_over_a_standing_run(self, decode_model,
                                                           kv_guard):
        cfg = _cfg(max_seq_len=128, max_new_tokens=100, kv_guard=kv_guard)
        sched = serving.DecodeScheduler(decode_model, cfg, autostart=False)
        rng = np.random.RandomState(4)
        futs = [sched.submit(p) for p in _prompts(4, rng, lo=3, hi=9)]
        c0 = _pipe_counters()
        sched.start()
        outs = [f.result(timeout=300) for f in futs]
        sched.stop()
        d = _pipe_delta(c0)
        assert all(len(o) == 100 for o in outs)
        assert d["tokens"] == 400 and d["tokens_discarded"] == 0
        if kv_guard:
            # the sweep must see a step's page before another write lands:
            # every step is read before the next goes out
            assert d["steps_overlapped"] == 0 and d["steps"] >= 99
        else:
            assert d["steps_overlapped"] / d["steps"] >= 0.95

    def test_no_compile_after_warmup_with_either_previous(self, decode_model):
        """``previous`` is a host array when nothing is in flight and the
        step before's output on the device when one is: warm-up compiles
        for both, so neither the first step of a run nor the ones behind it
        meet the compiler."""
        compiles = []

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(event)

        sched = serving.DecodeScheduler(decode_model, _cfg())
        c0 = compile_count()
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        try:
            rng = np.random.RandomState(6)
            d0 = _pipe_counters()
            for _ in range(2):          # two runs: two first steps
                futs = [sched.submit(p) for p in _prompts(5, rng)]
                for f in futs:
                    f.result(timeout=120)
            d = _pipe_delta(d0)
        finally:
            jax.monitoring.unregister_event_duration_listener(on_duration)
            sched.stop()
        assert 0 < d["steps_overlapped"] < d["steps"]
        assert compiles == [] and compile_count() == c0


# -- step programs are the model's ------------------------------------------

# -- a chunk's token read behind the step dispatched after it (ISSUE 51) -------

class _Lost:
    """A chunk's output whose readback fails."""

    def __array__(self, *args, **kwargs):
        from paddle_tpu.testing import faults

        raise faults.FaultInjected("injected lost chunk token")


def _tap(sched, events):
    """Note, in the loop's own order, each chunk sent / read (with the
    request's ``seq``), each decode step planned (with the ``seq`` of every
    sequence in it) and each decode step dispatched."""
    send, read = sched._send_chunk, sched._read_chunk
    plan, dispatch = sched._plan_step, sched._dispatch_step

    def tapped_send():
        sent = send()
        if sent is not None:
            events.append(("send", sent.slot.req.seq))
        return sent

    def tapped_read(sent, since=None):
        read(sent, since)
        events.append(("read", sent.slot.req.seq, bool(sent.slot.generated)))

    def tapped_plan():
        step = plan()
        if step is not None:
            events.append(("plan", {s.req.seq for _, s in step.entries}))
        return step

    def tapped_dispatch(step):
        events.append(("step",))
        return dispatch(step)

    sched._send_chunk, sched._read_chunk = tapped_send, tapped_read
    sched._plan_step, sched._dispatch_step = tapped_plan, tapped_dispatch


def _arrive_behind_a_commit(sched, prompts, first_new=40, new=4):
    """One request decodes; the others arrive beside it, on the scheduler's
    own clock: the worker waits behind the commit that gives the first
    request its third token until the others are in the queue, so they arrive
    beside a decoder however fast the loop is and however the host schedules
    the two threads."""
    reached, arrived = threading.Event(), threading.Event()
    commit, first = sched._commit_step, []

    def held_commit(sent):
        commit(sent)
        if first and len(first[0].token_times) >= 3 and not arrived.is_set():
            reached.set()
            arrived.wait(120)

    sched._commit_step = held_commit
    first.append(sched.submit(prompts[0], max_new_tokens=first_new))
    assert reached.wait(120)
    rest = [sched.submit(p, max_new_tokens=new) for p in prompts[1:]]
    arrived.set()
    return first + rest


class TestChunkReadBehindAStep:
    PROMPTS = [np.arange(1, 10, dtype=np.int32),
               np.arange(3, 43, dtype=np.int32) % 49 + 1,
               np.arange(7, 30, dtype=np.int32) % 49 + 1]

    def _cfg(self, **kw):
        return _cfg(prefill_chunk_tokens=16, **kw)

    @pytest.mark.parametrize("how", ["neighbours", "alone", "kv_guard"])
    def test_chunks_overlapped_counts_the_chunks_that_rode_a_step(
            self, decode_model, how):
        events = []
        rode0 = obs.counter("serving.decode.chunks_overlapped").value
        chunks0 = obs.counter("serving.decode.prefills").value
        sched = serving.DecodeScheduler(
            decode_model, self._cfg(kv_guard=how == "kv_guard"),
            autostart=False)
        _tap(sched, events)
        sched.start()
        try:
            if how == "alone":
                # each is served before the next arrives: nobody decodes
                # beside a chunk
                for p in self.PROMPTS:
                    sched.generate(p, max_new_tokens=4, timeout=120)
            else:
                for f in _arrive_behind_a_commit(sched, self.PROMPTS):
                    f.result(timeout=120)
        finally:
            sched.stop()
        kinds = [e[0] for e in events]
        sends = [i for i, k in enumerate(kinds) if k == "send"]
        # a chunk rode a step when one was dispatched before its token was read
        rode = sum(kinds[i + 1:kinds.index("read", i)].count("step") > 0
                   for i in sends)
        assert (obs.counter("serving.decode.prefills").value - chunks0
                == len(sends) == 1 + 3 + 2)
        assert (obs.counter("serving.decode.chunks_overlapped").value - rode0
                == rode)
        # all but the first request's own chunk, and none where nobody decodes
        assert rode == (len(sends) - 1 if how == "neighbours" else 0)

    def test_a_final_chunks_slot_misses_exactly_one_planned_step(
            self, decode_model):
        events = []
        want = (_free_run(decode_model, self.PROMPTS[:1], max_new_tokens=16)
                + _free_run(decode_model, self.PROMPTS[1:2], max_new_tokens=6))
        sched = serving.DecodeScheduler(decode_model, self._cfg(),
                                        autostart=False)
        _tap(sched, events)
        sched.start()
        try:
            futs = _arrive_behind_a_commit(sched, self.PROMPTS[:2],
                                           first_new=16, new=6)
            got = [f.result(timeout=120) for f in futs]
        finally:
            sched.stop()
        for g, w in zip(got, want):
            assert g.tobytes() == w.tobytes()
        late = futs[1].seq
        final = events.index(("read", late, True))
        sent = max(i for i, e in enumerate(events[:final])
                   if e == ("send", late))
        # the one step planned while the final chunk was in flight has no
        # seat for the sequence; the next one planned has
        behind = [e[1] for e in events[sent:final] if e[0] == "plan"]
        after = [e[1] for e in events[final:] if e[0] == "plan"]
        assert len(behind) == 1 and late not in behind[0]
        assert late in after[0]

    @pytest.mark.parametrize("pools", ["kept", "donated"])
    def test_a_chunk_read_that_fails_with_a_step_behind_it(self, decode_model,
                                                           pools):
        from paddle_tpu.testing import faults

        want = _free_run(decode_model, self.PROMPTS, max_new_tokens=12)
        sched = serving.DecodeScheduler(decode_model, self._cfg(),
                                        autostart=False)
        sched._donated = pools == "donated"      # the host's side of donation
        send, read = sched._send_chunk, sched._read_chunk
        fired, left, noted = [], [], threading.Event()

        def lossy_send():
            sent = send()
            if (sent is not None and sched._unread and not fired
                    and sent.slot.prompt_len == len(self.PROMPTS[1])):
                fired.append(sent.slot.req.seq)
                sent.out = _Lost()
            return sent

        def noting_read(sent, since=None):
            lost = isinstance(sent.out, _Lost)
            behind = len(sched._unread)
            read(sent, since)
            if lost:
                left.append((behind, len(sched._unread), [
                    s.inflight for s in sched._slots if s is not None],
                    bool(np.asarray(sched._cache.k_pool).any())))
                noted.set()

        sched._send_chunk, sched._read_chunk = lossy_send, noting_read
        sched.start()
        try:
            futs = _arrive_behind_a_commit(sched, self.PROMPTS, first_new=12,
                                           new=12)
            with pytest.raises(faults.FaultInjected, match="lost chunk"):
                futs[1].result(timeout=120)
            assert fired == [futs[1].seq]
            assert noted.wait(120)        # the future fails inside the read
            # a step had gone out behind the chunk: it is abandoned, and no
            # slot counts anything in flight
            (behind, unread, inflight, written), = left
            assert behind == 1 and unread == 0 and not any(inflight)
            if pools == "kept":
                # that sequence alone: the others stand on the cache as the
                # chunk found it and finish an undisturbed run's tokens
                assert len(inflight) >= 1 and written
                for f, w in zip(futs[::2], want[::2]):
                    assert f.result(timeout=120).tobytes() == w.tobytes()
            else:
                # as on the chip, the chunk had consumed the pools: every
                # seated sequence fails typed and the pools come back zeroed
                assert inflight == [] and not written
                with pytest.raises(faults.FaultInjected):
                    futs[0].result(timeout=120)
                try:        # seated by then, or served from the new pools
                    last = futs[2].result(timeout=120)
                except faults.FaultInjected:
                    pass
                else:
                    assert last.tobytes() == want[2].tobytes()
            got = sched.generate(self.PROMPTS[1], max_new_tokens=12,
                                 timeout=120)
            assert sched.stats()["kv_pages_used"] == 0
        finally:
            sched.stop()
        assert got.tobytes() == want[1].tobytes()

    @pytest.mark.parametrize("mode", ["solo", "pool"])
    def test_a_worker_killed_between_the_two_dispatches(self, decode_model,
                                                        mode):
        from paddle_tpu.testing import faults

        want = _free_run(decode_model, self.PROMPTS[:1], max_new_tokens=12)
        sched = serving.DecodeScheduler(
            decode_model, self._cfg(), autostart=False,
            evict_on_death=mode == "pool")
        send, dispatch = sched._send_chunk, sched._dispatch_step
        flying, killed = [], []

        def noting_send():
            sent = send()
            flying[:] = [sent] if sent is not None and sched._unread else []
            return sent

        def deadly_dispatch(step):
            if flying and not killed:
                killed.append((flying[0], flying[0].slot.prefill_pos))
                raise faults.WorkerKilled("injected kill behind a chunk")
            return dispatch(step)

        sched._send_chunk, sched._dispatch_step = noting_send, deadly_dispatch
        sched.start()
        try:
            futs = _arrive_behind_a_commit(sched, self.PROMPTS[:2],
                                           first_new=12)
            deadline = time.time() + 30
            while sched.alive and time.time() < deadline:
                time.sleep(0.005)
            assert not sched.alive and len(killed) == 1
            (sent, pos), = killed
            # what was planned behind the chunk is forgotten: a slot counts
            # in flight what is dispatched and unread, and nothing else
            assert sched._planned == []
            for slot in filter(None, sched._slots):
                assert slot.inflight == sum(
                    slot in [s for _, s in step.entries]
                    for step in sched._unread)
            if mode == "solo":
                with pytest.raises(serving.ServingDegraded,
                                   match="mid-prefill"):
                    futs[1].result(timeout=120)
                assert sched.restart()
                assert futs[0].result(timeout=120).tobytes() \
                    == want[0].tobytes()
                assert sched.stats()["kv_pages_used"] == 0
            else:
                # the slot is left as the chunk found it, for the pool
                assert sched._slots[sent.idx] is sent.slot
                assert sent.slot.prefilling and sent.slot.prefill_pos == pos
                harvested = sched.evict_inflight()
                assert {r.seq for r in harvested} == {f.seq for f in futs}
                assert not sched._unread and not any(sched._slots)
                assert sched.stats()["kv_pages_used"] == 0
        finally:
            sched.stop()


def _counting_model(base, calls, step_counters=()):
    """``base``'s step functions behind wrappers that count their PYTHON
    calls (one a trace) in ``calls``; with ``step_counters`` both programs
    return one count a name beside what they returned."""
    import jax.numpy as jnp

    def wrap(kind, fn):
        def counted(*args):
            calls[kind] += 1
            out = fn(*args)
            if step_counters:
                out += (jnp.ones((len(step_counters),), jnp.int32),)
            return out
        return counted

    return serving.DecodeModel(
        wrap("decode", base.decode_fn), wrap("chunk", base.prefill_chunk_fn),
        params=base.params, num_layers=base.num_layers,
        num_heads=base.num_heads, head_dim=base.head_dim,
        vocab_size=base.vocab_size, step_counters=step_counters)


class TestStepProgramsAreTheModels:
    def test_two_schedulers_over_one_model_trace_once(self, decode_model):
        """Two schedulers over ONE model object dispatch the model's own
        jitted callables: the second enters ``decode_fn`` /
        ``prefill_chunk_fn`` for no shape the first already traced, and both
        serve what two schedulers over two model objects serve."""
        rng = np.random.RandomState(11)
        prompts = _prompts(6, rng)
        cfg = dict(prefill_chunk_tokens=16)

        def serve(model):
            sched = serving.DecodeScheduler(model, _cfg(**cfg))
            try:
                return sched, [f.result(timeout=120).tobytes() for f in
                               [sched.submit(p) for p in prompts]]
            finally:
                sched.stop()

        calls = collections.Counter()
        shared = _counting_model(decode_model, calls)
        first, got_first = serve(shared)
        shapes = {"decode": 1, "chunk": len(first._chunk_widths())}
        assert dict(calls) == shapes      # once a shape, warm-up included
        second, got_second = serve(shared)
        assert dict(calls) == shapes      # the second traced nothing
        for key in (("decode",), ("chunk", 16), ("chunk", 8)):
            assert first._jit.get(key) is second._jit.get(key)
        assert first._jit.get(("chunk", 8)) is first._jit.get(("chunk", 16))
        apart = collections.Counter()
        got_apart = [serve(_counting_model(decode_model, apart))[1]
                     for _ in range(2)]
        assert {k: 2 * n for k, n in shapes.items()} == dict(apart)
        assert got_first == got_second == got_apart[0] == got_apart[1]

    def test_chunk_counts_come_back_with_the_program(self, decode_model):
        """Whether the chunk program counts is a fact of the model's program,
        known once ANY scheduler has traced it: a second scheduler over the
        model, which traces nothing, labels its step counters ``chunk``."""
        calls = collections.Counter()
        model = _counting_model(decode_model, calls, ("toy.rides",))
        assert model.step_programs(None, False).chunk_counts is None
        serving.DecodeScheduler(model, _cfg(), autostart=False)   # warms up
        traced = dict(calls)
        assert model.step_programs(None, False).chunk_counts is True
        cells = [obs.counter("serving.decode.toy.rides", {"chunk": c})
                 for c in (0, 1)]
        before = [c.value for c in cells]
        plain = obs.counter("serving.decode.toy.rides").value
        sched = serving.DecodeScheduler(model, _cfg())
        out = sched.generate(np.arange(1, 12, dtype=np.int32),
                             max_new_tokens=4, timeout=120)
        sched.stop()
        assert dict(calls) == traced and len(out) == 4
        # one chunk (an 11-token prompt in its 16-wide bucket), and a
        # decode step a token behind the chunk's own (the last one may ride
        # a step whose token is dropped)
        assert cells[1].value - before[1] == 1
        assert cells[0].value - before[0] >= 3
        assert obs.counter("serving.decode.toy.rides").value == plain


# -- one host upload a dispatch (ISSUE 59) -----------------------------------

class _ByArgument:
    """The step programs as they were before ISSUE 59, a value an argument:
    the reference the packed ones (``step_programs.py``) are held to."""

    def __init__(self, model, top_k):
        import jax.numpy as jnp

        from paddle_tpu.serving.step_programs import sample_token

        def decode(params, pools, tokens, positions, tables, kv_lens, seeds,
                   temps, previous, from_previous):
            tokens = jnp.where(from_previous, previous[:tokens.shape[0]],
                               tokens)
            logits, pools, *_ = model.decode_fn(
                params, tokens, positions, pools, tables, kv_lens)

            def samp(logit, seed, pos, temp):
                k = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
                return sample_token(logit, k, temp, top_k)

            return jax.vmap(samp)(logits, seeds, kv_lens, temps), pools

        def chunk(params, pools, tokens, start, valid, chunk_pages,
                  gather_pages, slot, seed, temp):
            logits, pools, *_ = model.prefill_chunk_fn(
                params, tokens, start, valid, pools, chunk_pages,
                gather_pages, slot)
            kk = jax.random.fold_in(jax.random.PRNGKey(seed), start + valid)
            return sample_token(logits, kk, temp, top_k), pools

        self.decode, self.chunk = jax.jit(decode), jax.jit(chunk)


def _serve_by_argument(sched, monkeypatch, mixed):
    """Make ``sched``'s loop dispatch ``_ByArgument``'s programs: its packed
    buffers are taken apart on the HOST and handed over a value an argument.
    ``mixed`` gathers the decode steps in which one live slot took its token
    from the step before and another from the host."""
    from paddle_tpu.serving.step_programs import split_chunk, split_step

    ref = _ByArgument(sched.model, sched.config.top_k)
    groups = tuple(sched.model.page_groups)

    def by_group(arrays):
        arrays = [np.ascontiguousarray(a) for a in arrays]
        return dict(zip(groups, arrays)) if groups else arrays[0]

    def decode(params, pools, packed, previous, widths=None):
        tables, columns = split_step(np.asarray(packed), widths)
        tokens, positions, kv_lens, seeds, temps, from_previous = (
            np.ascontiguousarray(c) for c in columns)
        live = from_previous[kv_lens > 0]
        if live.any() and not live.all():
            mixed.append(live)
        return ref.decode(params, pools, tokens, positions, by_group(tables),
                          kv_lens, seeds.view(np.uint32),
                          temps.view(np.float32), previous,
                          from_previous != 0)

    def chunk(params, pools, packed, sizes):
        tokens, scalars, vecs = split_chunk(np.asarray(packed), sizes)
        start, valid, slot = (np.int32(x) for x in scalars[:3])
        seed, temp = np.ascontiguousarray(scalars[3:])[:, None]
        return ref.chunk(params, pools, np.ascontiguousarray(tokens), start,
                         valid, by_group([w for w, _ in vecs]),
                         by_group([g for _, g in vecs]), slot,
                         seed.view(np.uint32)[0], temp.view(np.float32)[0])

    monkeypatch.setattr(
        sched._jit, "get",
        lambda key: decode if tuple(key) == ("decode",) else chunk)


def _toy_groups():
    import test_kv_cache_groups as G

    return G._model(), G._config(num_slots=3, max_new_tokens=12), G.V


def _upload_scenario(name, decode_model):
    """``(model, config, waves)``: ``waves`` are lists of ``(prompt,
    submit kwargs)`` sent together, each wave behind the one before."""
    rng = np.random.RandomState(59)
    model, cfg, vocab = decode_model, _cfg(prefill_chunk_tokens=16), 50
    if name == "two_groups":
        model, cfg, vocab = _toy_groups()

    def some(n, **kw):
        return [(p, dict(kw)) for p in _prompts(n, rng, vocab=vocab)]

    if name == "greedy" or name == "two_groups":
        return model, cfg, [some(6)]
    if name == "sampled":
        return model, cfg, [[(p, dict(temperature=0.6 + 0.2 * i,
                                      seed=(7919 * i + 2 ** 31) % 2 ** 32))
                             for i, (p, _) in enumerate(some(6))]]
    if name == "prefix_hit":
        head = rng.randint(1, 50, size=24).astype(np.int32)
        tails = _prompts(3, rng, lo=2, hi=9)
        return model, _cfg(prefill_chunk_tokens=16, prefix_cache=True), [
            [(np.concatenate([head, t]), {})] for t in tails]
    assert name == "sits_out"
    # answers of different lengths over prompts of one to three chunks: a
    # slot joins, and another reaches its length, while the others decode
    return model, cfg, [[
        (p, dict(max_new_tokens=n, temperature=t, seed=11 + n))
        for (p, _), n, t in zip(some(6), (3, 12, 5, 9, 2, 7),
                                (0.0, 0.8, 0.0, 1.1, 0.7, 0.0))]]


class TestOneUploadADispatch:
    @pytest.mark.parametrize("scenario", [
        "greedy", "sampled", "prefix_hit", "sits_out", "two_groups"])
    def test_packed_programs_serve_the_tokens_of_a_value_an_argument(
            self, decode_model, monkeypatch, scenario):
        """The same requests through the packed programs and through the
        programs that took every value as an argument of its own: bitwise
        the same tokens, for greedy and sampled requests, behind a
        prefix-cache hit, with slots joining and leaving between steps, and
        for a model in two page groups."""
        model, cfg, waves = _upload_scenario(scenario, decode_model)
        hits = obs.counter("serving.decode.kv_hit_pages")
        served, mixed = {}, []
        for form in ("packed", "by_argument"):
            hit0 = hits.value
            sched = serving.DecodeScheduler(model, cfg, autostart=False)
            if form == "by_argument":
                _serve_by_argument(sched, monkeypatch, mixed)
            sched.start()
            try:
                served[form] = [
                    [f.result(timeout=120).tobytes() for f in
                     [sched.submit(p, **kw) for p, kw in wave]]
                    for wave in waves]
            finally:
                sched.stop()
            if scenario == "prefix_hit":
                assert hits.value - hit0 >= 2 * (24 // cfg.page_size)
        assert served["packed"] == served["by_argument"]
        if scenario != "prefix_hit":
            assert mixed, "no step mixed host tokens with the device's"

    def test_every_dispatch_uploads_one_host_array(self, decode_model):
        """``serving.decode.host_uploads`` moves by one a decode dispatch and
        by one a chunk dispatch, warm-up's included: the buffer is the only
        host array a step program is handed."""
        cells = {p: obs.counter("serving.decode.host_uploads", {"program": p})
                 for p in ("decode", "chunk")}
        spans = {"decode": obs.histogram("serving.decode.step.dispatch"),
                 "chunk": obs.histogram("serving.decode.prefill.dispatch")}
        before = {p: c.value for p, c in cells.items()}
        sched = serving.DecodeScheduler(
            decode_model, _cfg(prefill_chunk_tokens=16), autostart=False)
        warmed = {"decode": 2, "chunk": len(sched._chunk_widths())}
        assert {p: c.value - before[p] for p, c in cells.items()} == warmed
        sent = {p: h.snapshot().count for p, h in spans.items()}
        sched.start()
        rng = np.random.RandomState(2)
        for f in [sched.submit(p) for p in _prompts(5, rng, hi=40)]:
            f.result(timeout=120)
        sched.stop()
        for p, cell in cells.items():
            dispatched = spans[p].snapshot().count - sent[p]
            assert dispatched > 0
            assert cell.value - before[p] - warmed[p] == dispatched, p

    @pytest.mark.parametrize("arrays", ["host", "device"])
    def test_run_step_by_argument_leaves_the_loops_cache_rows(
            self, decode_model, arrays):
        """``run_step`` keeps its argument lists (a check hands it the values
        one by one, as host or device arrays) and packs them as the loop
        does: a prompt prefilled and decoded through it leaves, bit for bit,
        the rows and the tokens that the loop leaves."""
        import jax.numpy as jnp

        put = np.asarray if arrays == "host" else jnp.asarray
        cfg = _cfg(prefill_chunk_tokens=16)
        prompt = np.arange(3, 3 + 21, dtype=np.int32) % 50
        new, temp, seed = 6, 0.9, 2 ** 32 - 5

        loop = serving.DecodeScheduler(decode_model, cfg, autostart=False)
        took, alloc = [], loop.cache.alloc
        loop.cache.alloc = lambda n: took.append(alloc(n)) or took[-1]
        loop.start()
        want = loop.generate(prompt, max_new_tokens=new, temperature=temp,
                             seed=seed, timeout=120)
        loop.stop()
        (pages,) = took

        sched = serving.DecodeScheduler(decode_model, cfg, autostart=False)
        cache, S = sched.cache, cfg.num_slots
        assert cache.alloc(len(pages)) == pages
        tables = np.zeros((S, cache.max_pages_per_seq), np.int32)
        tables[1] = cache.table_row(pages)          # seated in slot 1
        ps, got = cfg.page_size, []
        for start, width in ((0, 16), (16, 8)):
            valid = min(width, len(prompt) - start)
            tokens = np.zeros((width,), np.int32)
            tokens[:valid] = prompt[start:start + valid]
            tok = sched.run_step(
                ("chunk", width), put(tokens), put(np.int32(start)),
                put(np.int32(valid)),
                put(np.asarray(pages[start // ps:(start + width) // ps],
                               np.int32)),
                put(tables[1]), np.int32(1), put(np.uint32(seed)),
                put(np.float32(temp)))
        got.append(int(tok))
        zeros = np.zeros((S,), np.int32)
        seeds = np.full((S,), seed, np.uint32)
        temps = np.full((S,), temp, np.float32)
        for at in range(len(prompt), len(prompt) + new - 1):
            tokens, positions, kv_lens = (zeros.copy() for _ in range(3))
            tokens[1], positions[1], kv_lens[1] = got[-1], at, at + 1
            step_tables = np.zeros_like(tables)
            step_tables[1] = tables[1]
            out = sched.run_step(
                ("decode",), put(tokens), put(positions), put(step_tables),
                put(kv_lens), put(seeds), put(temps))
            got.append(int(np.asarray(out)[1]))
        assert got == list(want)
        rows = len(prompt) + new - 1
        for leaf in ("k", "v"):
            a, b = (np.asarray(c.pools[leaf])[:, pages].reshape(
                decode_model.num_layers, -1, c.pools[leaf].shape[-1])[:, :rows]
                for c in (loop.cache, cache))
            np.testing.assert_array_equal(a, b)

    def test_no_compile_in_the_loop_for_any_chunk_width(self, decode_model):
        """After ``warmup()`` the loop asks jax for no compile, whatever
        chunk width a prompt's remainder takes: warm-up's buffers live where
        the loop's do."""
        obs.watch_compiles()
        asked = obs.counter("xla.compile.requests",
                            {"within": "serving.decode.iteration"})
        sched = serving.DecodeScheduler(
            decode_model, _cfg(prefill_chunk_tokens=32, max_seq_len=128),
            autostart=False)
        widths = sched._chunk_widths()
        assert widths == (8, 16, 32)
        before, keys, get = asked.value, set(), sched._jit.get
        sched._jit.get = lambda key: keys.add(tuple(key)) or get(key)
        sched.start()
        rng = np.random.RandomState(8)
        futs = [sched.submit(rng.randint(1, 50, size=n).astype(np.int32),
                             temperature=t, seed=n)
                for n, t in ((5, 0.0), (12, 0.7), (30, 0.0), (32 + 11, 1.0),
                             (64 + 3, 0.0))]
        for f in futs:
            assert len(f.result(timeout=120)) == 8
        sched.stop()
        assert keys == {("decode",)} | {("chunk", w) for w in widths}
        assert asked.value == before


# -- engine integration ------------------------------------------------------

class TestEngineGenerate:
    def test_generate_async_and_health(self, decode_model):
        eng = serving.InferenceEngine(decode_model=decode_model,
                                      decode_config=_cfg())
        futs = [eng.generate_async(np.array([3, 4, 5], np.int32),
                                   max_new_tokens=3) for _ in range(4)]
        outs = [f.result(timeout=120) for f in futs]
        assert all(o.tobytes() == outs[0].tobytes() for o in outs)
        h = eng.health()
        assert h["decode"]["completed"] == 4
        assert h["model_version"] is None  # generate-only engine
        with pytest.raises(serving.ServingError, match="predict"):
            eng.predict({"x": np.zeros((1, 4), "float32")})
        with pytest.raises(serving.ServingError, match="swap"):
            eng.swap_model("/nonexistent")
        eng.stop()
        with pytest.raises(serving.ServingClosed):
            eng.generate(np.array([1], np.int32))

    def test_engine_without_decode_model_refuses_generate(self, tmp_path):
        with pytest.raises(ValueError, match="model_dir"):
            serving.InferenceEngine()


# -- sampling (temperature / top-k / carried PRNG key) -----------------------

class TestSampling:
    def test_greedy_default_unchanged_and_deterministic(self, decode_model):
        rng = np.random.RandomState(3)
        p = _prompts(1, rng)[0]
        sched = serving.DecodeScheduler(decode_model, _cfg())
        a = sched.generate(p, timeout=120)
        b = sched.generate(p, timeout=120, temperature=0.0, seed=123)
        sched.stop()
        # temperature 0 is argmax whatever the seed; None defaults to it
        assert a.tobytes() == b.tobytes()

    def test_same_seed_reproduces_other_seed_differs(self, decode_model):
        rng = np.random.RandomState(4)
        p = _prompts(1, rng, lo=8, hi=9)[0]
        sched = serving.DecodeScheduler(decode_model, _cfg())
        a = sched.generate(p, timeout=120, temperature=1.0, seed=7)
        b = sched.generate(p, timeout=120, temperature=1.0, seed=7)
        outs = [sched.generate(p, timeout=120, temperature=1.0, seed=s)
                for s in range(8)]
        sched.stop()
        assert a.tobytes() == b.tobytes(), "same (seed, prompt) differs"
        assert len({o.tobytes() for o in outs}) > 1, (
            "8 seeds all produced identical sampled sequences")

    def test_sampling_independent_of_batch_composition(self, decode_model):
        """The carried key is folded with the token's absolute position,
        so a sampled request decodes identically whether it shares the
        step with neighbors (continuous batching) or runs alone."""
        rng = np.random.RandomState(5)
        p = _prompts(1, rng, lo=10, hi=11)[0]
        solo = serving.DecodeScheduler(decode_model, _cfg(max_active=1))
        want = solo.generate(p, timeout=120, temperature=0.9, seed=11)
        solo.stop()
        packed = serving.DecodeScheduler(decode_model, _cfg())
        futs = [packed.submit(q, temperature=0.7, seed=100 + i)
                for i, q in enumerate(_prompts(3, rng))]
        got = packed.generate(p, timeout=120, temperature=0.9, seed=11)
        for f in futs:
            f.result(timeout=120)
        packed.stop()
        assert got.tobytes() == want.tobytes()

    def test_top_k_and_validation(self, decode_model):
        rng = np.random.RandomState(6)
        p = _prompts(1, rng)[0]
        sched = serving.DecodeScheduler(
            decode_model, _cfg(num_slots=2, top_k=5))
        greedy = sched.generate(p, timeout=120)
        sampled = sched.generate(p, timeout=120, temperature=0.8, seed=2)
        with pytest.raises(serving.ServingError, match="temperature"):
            sched.submit(p, temperature=-0.5)
        sched.stop()
        assert greedy.shape == sampled.shape
        with pytest.raises(ValueError, match="top_k"):
            serving.DecodeConfig(top_k=0)
        with pytest.raises(ValueError, match="default_temperature"):
            serving.DecodeConfig(default_temperature=-1.0)

    def test_default_temperature_config(self, decode_model):
        rng = np.random.RandomState(7)
        p = _prompts(1, rng, lo=6, hi=7)[0]
        sched = serving.DecodeScheduler(
            decode_model, _cfg(default_temperature=1.0))
        # seedless sampling defaults its seed to the admission seq:
        # stable within a run, so two identical submits may differ
        # (different seqs) but an explicit seed pins them
        a = sched.generate(p, timeout=120, seed=5)
        b = sched.generate(p, timeout=120, seed=5)
        g = sched.generate(p, timeout=120, temperature=0.0)
        sched.stop()
        assert a.tobytes() == b.tobytes()
        assert g.shape == a.shape


# -- chunked prefill (ISSUE 15a) ---------------------------------------------

class TestChunkedPrefill:
    def test_chunked_equals_monolithic_bitwise(self, decode_model):
        rng = np.random.RandomState(21)
        prompts = _prompts(8, rng, lo=2, hi=50)
        outs = {}
        for name, kw in (("monolithic", {}),
                         ("chunked", {"prefill_chunk_tokens": 8})):
            sched = serving.DecodeScheduler(decode_model, _cfg(**kw))
            futs = [sched.submit(p) for p in prompts]
            outs[name] = [f.result(timeout=120) for f in futs]
            assert sched.stats()["kv_pages_used"] == 0
            sched.stop()
        for i, (a, b) in enumerate(zip(outs["monolithic"], outs["chunked"])):
            assert a.tobytes() == b.tobytes(), (
                "sequence %d differs chunked vs monolithic" % i)

    def test_no_recompiles_with_chunking(self, decode_model):
        sched = serving.DecodeScheduler(
            decode_model, _cfg(prefill_chunk_tokens=16))
        rng = np.random.RandomState(22)
        c0 = compile_count()
        futs = [sched.submit(p) for p in _prompts(6, rng, hi=40)]
        for f in futs:
            f.result(timeout=120)
        assert compile_count() == c0, "chunked prefill recompiled"
        sched.stop()

    def test_config_validation(self, decode_model):
        with pytest.raises(ValueError, match="prefill_chunk_tokens"):
            serving.DecodeConfig(page_size=8, prefill_chunk_tokens=12)
        with pytest.raises(ValueError, match="prefill_chunk_tokens"):
            serving.DecodeConfig(page_size=8, prefill_chunk_tokens=4)

    def test_decode_model_needs_a_chunk_function(self, decode_model):
        """Every model prefills through the chunk program: a model without
        one is refused where it is made, not by a scheduler later."""
        sizes = dict(params=decode_model.params,
                     num_layers=decode_model.num_layers,
                     num_heads=decode_model.num_heads,
                     head_dim=decode_model.head_dim,
                     vocab_size=decode_model.vocab_size)
        with pytest.raises(TypeError, match="prefill_chunk_fn"):
            serving.DecodeModel(decode_model.decode_fn, **sizes)
        whole = serving.DecodeModel(
            decode_model.decode_fn, decode_model.prefill_chunk_fn, **sizes)
        # the two step programs and no third
        sched = serving.DecodeScheduler(whole, _cfg(warmup=False),
                                        autostart=False)
        with pytest.raises(KeyError):
            sched._build_step(("prefill", 16), donate=False)

    def test_mid_prefill_deadline_shed(self, decode_model):
        from paddle_tpu.testing import faults

        sched = serving.DecodeScheduler(
            decode_model, _cfg(prefill_chunk_tokens=8), autostart=False)
        mid0 = obs.counter("serving.decode.expired_mid_prefill").value
        with faults.slow_execute(0.01):
            doomed = sched.submit(np.arange(1, 49, dtype=np.int32),
                                  max_new_tokens=8, deadline_ms=25)
            sched.start()
            deadline = time.perf_counter() + 30
            while (obs.counter(
                    "serving.decode.expired_mid_prefill").value <= mid0
                   and time.perf_counter() < deadline):
                time.sleep(0.01)
            with pytest.raises(serving.ServingTimeout, match="mid-prefill"):
                doomed.result(timeout=120)
        assert obs.counter("serving.decode.expired_mid_prefill").value \
            == mid0 + 1
        assert sched.stats()["kv_pages_used"] == 0
        # still serves after the shed
        assert sched.generate(np.array([1, 2], np.int32), max_new_tokens=2,
                              timeout=120).shape == (2,)
        sched.stop()

    def test_stats_report_chunk_config(self, decode_model):
        sched = serving.DecodeScheduler(
            decode_model,
            _cfg(prefill_chunk_tokens=16, prefix_cache=True, warmup=False),
            autostart=False)
        st = sched.stats()
        assert st["prefill_chunk_tokens"] == 16
        assert st["prefix_cache"] is True
        assert "kv_hit_pages" in st["prefix"]
        sched.stop()


# -- prefix caching (ISSUE 15b) ----------------------------------------------

class TestPrefixCache:
    def test_warm_equals_cold_bitwise_with_hits(self, decode_model):
        rng = np.random.RandomState(31)
        prefix = rng.randint(1, 50, size=24).astype(np.int32)
        prompts = [np.concatenate([prefix,
                                   rng.randint(1, 50, size=4)
                                   .astype(np.int32)]) for _ in range(5)]
        hit = obs.counter("serving.decode.kv_hit_pages")
        pt = obs.counter("serving.decode.prefill_tokens")
        outs = {}
        for name, kw in (("cold", {}), ("warm", {"prefix_cache": True})):
            sched = serving.DecodeScheduler(decode_model, _cfg(**kw))
            h0, p0 = hit.value, pt.value
            outs[name] = [sched.generate(p, timeout=120) for p in prompts]
            assert sched.stats()["kv_pages_used"] == 0
            if name == "warm":
                assert hit.value - h0 > 0, "no page hits on shared prefix"
                warm_tokens = pt.value - p0
            else:
                cold_tokens = pt.value - p0
            sched.stop()
        for a, b in zip(outs["cold"], outs["warm"]):
            assert a.tobytes() == b.tobytes()
        assert warm_tokens < cold_tokens

    def test_last_token_always_prefills(self, decode_model):
        # a fully page-aligned, fully cached prompt still prefills >= 1
        # token: the first sampled token's logits exist in no cache
        pt = obs.counter("serving.decode.prefill_tokens")
        sched = serving.DecodeScheduler(decode_model,
                                        _cfg(prefix_cache=True))
        prompt = np.arange(1, 17, dtype=np.int32)  # exactly 2 pages
        sched.generate(prompt, max_new_tokens=2, timeout=120)
        p0 = pt.value
        out = sched.generate(prompt, max_new_tokens=2, timeout=120)
        assert out.shape == (2,)
        # second run reuses page 0 but must re-run the LAST page (the
        # reuse cap is len(prompt) - 1 tokens)
        assert pt.value - p0 == 8
        sched.stop()

    def test_eviction_under_pressure_serves_correctly(self, decode_model):
        rng = np.random.RandomState(33)
        prompts = _prompts(5, rng, lo=30, hi=40)
        ev = obs.counter("serving.decode.kv_evictions")
        e0 = ev.value
        small = _cfg(prefix_cache=True, num_pages=12)
        sched = serving.DecodeScheduler(decode_model, small)
        got = [sched.generate(p, timeout=120) for p in prompts]
        assert sched.stats()["kv_pages_used"] == 0
        sched.stop()
        assert ev.value - e0 > 0, "undersized pool never evicted"
        ref = serving.DecodeScheduler(decode_model, _cfg())
        want = [ref.generate(p, timeout=120) for p in prompts]
        ref.stop()
        for a, b in zip(got, want):
            assert a.tobytes() == b.tobytes()

    def test_parked_hol_probes_once(self, decode_model):
        # a head-of-line request parked on pool exhaustion carries its
        # prefix-probe result (pages pinned) instead of re-probing every
        # iteration — the hit/miss counters must move ONCE per admission
        miss = obs.counter("serving.decode.kv_miss_pages")
        cfg = _cfg(prefix_cache=True, num_pages=8, num_slots=2)
        sched = serving.DecodeScheduler(decode_model, cfg)
        m0 = miss.value
        # A reserves 6 of the 7 usable pages and decodes for many
        # iterations; B (4 pages) parks behind it the whole time
        a = sched.submit(np.arange(1, 17, dtype=np.int32),
                         max_new_tokens=32)
        b = sched.submit(np.arange(30, 47, dtype=np.int32),  # disjoint
                         max_new_tokens=8)
        a.result(timeout=120)
        b.result(timeout=120)
        sched.stop()
        # one probe each: A misses (16-1)//8 = 1 page, B (17-1)//8 = 2
        assert miss.value - m0 == 3, (
            "parked HOL request re-probed the prefix index (misses "
            "counted %d, expected 3)" % (miss.value - m0))

    def test_kv_cache_prefix_unit(self):
        c = serving.PagedKVCache(1, num_pages=9, page_size=4, num_heads=2,
                                 head_dim=8, max_seq_len=32)
        toks = np.arange(100, 113, dtype=np.int32)  # 13 tokens: 3 full pages
        pages, hashes = c.lookup_prefix(toks)
        assert pages == [] and len(hashes) == 3
        owned = c.alloc(4)
        for i in range(3):
            assert c.register_prefix(hashes, i, owned[i])
        # duplicate registration (another writer) is refused
        assert not c.register_prefix(hashes, 0, owned[3])
        c.free(owned)
        assert c.used_pages == 0 and c.cached_pages == 3
        # a second identical prompt hits the whole reusable prefix
        # (capped at len - 1 = 12 tokens = 3 pages)
        pages2, _ = c.lookup_prefix(toks)
        assert pages2 == owned[:3] and c.used_pages == 3
        # a prompt that diverges at page 1 reuses only page 0
        toks3 = toks.copy()
        toks3[5] = 999
        c.free(pages2)
        pages3, _ = c.lookup_prefix(toks3)
        assert pages3 == owned[:1]
        c.free(pages3)
        # pressure: allocating everything evicts the LRU parked pages
        ev0 = obs.counter("serving.decode.kv_evictions").value
        big = c.alloc(8)
        assert len(big) == 8
        assert obs.counter("serving.decode.kv_evictions").value - ev0 == 3
        assert c.lookup_prefix(toks)[0] == []  # index flushed by eviction
        c.free(big)


# -- prefill retry (the replayable decode leg) -------------------------------

class TestPrefillRetry:
    def test_transient_prefill_fault_retried_to_success(self, decode_model):
        from paddle_tpu.testing import faults

        rng = np.random.RandomState(8)
        p = _prompts(1, rng)[0]
        sched = serving.DecodeScheduler(decode_model, _cfg())
        want = sched.generate(p, timeout=120)
        r0 = obs.counter("serving.decode.prefill_retries").value
        with faults.flaky_execute(times=2) as fired:
            got = sched.generate(p, timeout=120)
        sched.stop()
        assert fired[0] == 2
        assert got.tobytes() == want.tobytes(), (
            "retried prefill changed the generated tokens")
        assert obs.counter("serving.decode.prefill_retries").value == r0 + 2

    def test_fatal_prefill_fault_fails_typed_without_retry(self, decode_model):
        from paddle_tpu.testing import faults

        rng = np.random.RandomState(9)
        p = _prompts(1, rng)[0]
        sched = serving.DecodeScheduler(decode_model, _cfg())
        r0 = obs.counter("serving.decode.prefill_retries").value
        with faults.poison_request(lambda r: True):
            fut = sched.submit(p)
            with pytest.raises(ValueError):
                fut.result(timeout=120)
        # fatal (non-transient) faults are not retried
        assert obs.counter("serving.decode.prefill_retries").value == r0
        # and the scheduler still serves afterwards
        out = sched.generate(p, timeout=120)
        sched.stop()
        assert out.shape[0] >= 1

    def test_retry_exhaustion_fails_typed(self, decode_model):
        from paddle_tpu.testing import faults

        rng = np.random.RandomState(10)
        p = _prompts(1, rng)[0]
        sched = serving.DecodeScheduler(decode_model, _cfg())
        with faults.flaky_execute(times=None):   # every attempt faults
            fut = sched.submit(p)
            with pytest.raises(faults.FaultInjected):
                fut.result(timeout=120)
        out = sched.generate(p, timeout=120)     # scheduler survived
        sched.stop()
        assert out.shape[0] >= 1
