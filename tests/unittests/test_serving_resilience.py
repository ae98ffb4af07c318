"""Serving self-healing units: circuit breaker, resilient dispatcher
(retry + poison bisection), batcher stop/death semantics, the worker
supervisor, the serving chaos injectors, and the engine-level degraded
state machine.

The end-to-end overload choreography (open-loop arrivals, goodput by
priority class, chaos composition) is gated by tools/check_slo.py via
test_slo_gate.py; these tests pin the per-component contracts."""
import functools
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.serving.batcher import DynamicBatcher
from paddle_tpu.serving.request_queue import Request
from paddle_tpu.serving.resilient import (
    CircuitBreaker,
    ResilientDispatcher,
    WorkerSupervisor,
)
from paddle_tpu.testing import faults

BUCKETS = (2, 4)


def _save_model(dirname, seed=17):
    fluid.unique_name.switch()
    main = fluid.Program()
    startup = fluid.Program()
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h = fluid.layers.fc(x, size=16, act="relu")
        out = fluid.layers.fc(h, size=4, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        np.random.seed(seed)
        exe.run(startup)
        fluid.io.save_inference_model(dirname, ["x"], [out], exe,
                                      main_program=main)
    return dirname


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    return _save_model(str(tmp_path_factory.mktemp("resil") / "model"))


def _req(rows=1, priority=None):
    return Request({"x": np.zeros((rows, 8), "float32")}, rows,
                   priority=priority)


# -- circuit breaker ---------------------------------------------------------

class TestCircuitBreaker:
    def test_trips_on_consecutive_fatal_and_half_open_recovers(self):
        clock = [0.0]
        b = CircuitBreaker(threshold=3, cooldown_s=1.0,
                           clock=lambda: clock[0])
        assert b.state == "closed" and b.allow()
        b.record_fatal()
        b.record_fatal()
        b.record_success()       # success resets the consecutive count
        b.record_fatal()
        b.record_fatal()
        assert b.state == "closed"
        b.record_fatal()         # third consecutive -> open
        assert b.state == "open" and not b.allow()
        clock[0] = 0.5
        assert not b.allow()     # cooldown not elapsed
        clock[0] = 1.1
        assert b.state == "half_open"
        assert b.allow()         # the probe
        assert not b.allow()     # only ONE probe in flight
        b.record_success()
        assert b.state == "closed" and b.allow()

    def test_half_open_fatal_reopens_with_fresh_cooldown(self):
        clock = [0.0]
        b = CircuitBreaker(threshold=1, cooldown_s=1.0,
                           clock=lambda: clock[0])
        b.record_fatal()
        assert b.state == "open"
        clock[0] = 1.5
        assert b.allow()         # half-open probe
        b.record_fatal()
        assert b.state == "open"
        clock[0] = 2.0           # only 0.5s into the NEW cooldown
        assert not b.allow()
        clock[0] = 2.6
        assert b.allow()

    def test_disabled_breaker_never_opens(self):
        b = CircuitBreaker(threshold=None)
        for _ in range(50):
            b.record_fatal()
            assert b.allow() and b.state == "closed"

    def test_state_gauge_published(self):
        g = obs.gauge("test.breaker_state_private")
        b = CircuitBreaker(threshold=1, cooldown_s=99.0, state_gauge=g)
        assert g.value == 0
        b.record_fatal()
        assert g.value == 1
        # the shared default cell is last-writer-wins across co-hosted
        # engines: constructing another breaker must NOT zero a live
        # breaker's open signal
        g2 = obs.gauge("test.breaker_state_private2")
        CircuitBreaker(threshold=1, state_gauge=g2).record_fatal()
        assert g2.value == 1
        CircuitBreaker(threshold=1, state_gauge=g2)
        assert g2.value == 1

    def test_probe_lease_expires_when_probe_never_dispatches(self):
        clock = [0.0]
        b = CircuitBreaker(threshold=1, cooldown_s=1.0,
                           clock=lambda: clock[0])
        b.record_fatal()
        clock[0] = 1.2
        assert b.allow()          # probe admitted...
        assert not b.allow()      # ...slot held...
        clock[0] = 2.3            # ...but the probe never dispatched
        assert b.allow()          # lease expired: a fresh probe may try
        b.record_success()
        assert b.state == "closed"


# -- resilient dispatcher ----------------------------------------------------

class _ScriptedExecute:
    """Completes every request, unless told to fail this attempt or a
    poison request is present (fails fatally)."""

    def __init__(self, transient_failures=0, poison=()):
        self.transient_failures = transient_failures
        self.poison = set(poison)
        self.calls = []

    def __call__(self, requests):
        self.calls.append([id(r) for r in requests])
        if self.transient_failures > 0:
            self.transient_failures -= 1
            raise faults.FaultInjected("flaky runtime")
        bad = [r for r in requests if id(r) in self.poison]
        if bad:
            raise ValueError("poison request")
        for r in requests:
            r.complete(["ok"])


class TestResilientDispatcher:
    def test_transient_retry_recovers_bitwise_and_counts(self):
        exe = _ScriptedExecute(transient_failures=2)
        d = ResilientDispatcher(exe, max_retries=2, sleep=lambda s: None)
        r0 = obs.counter("serving.retries").value
        reqs = [_req() for _ in range(3)]
        ok, failed = d(reqs)
        assert (ok, failed) == (3, 0)
        assert all(r.result(timeout=0) == ["ok"] for r in reqs)
        assert obs.counter("serving.retries").value == r0 + 2
        assert len(exe.calls) == 3  # 2 failed attempts + 1 success

    def test_poison_bisected_innocents_survive(self):
        reqs = [_req() for _ in range(8)]
        poison = reqs[5]
        exe = _ScriptedExecute(poison=[id(poison)])
        d = ResilientDispatcher(exe, max_retries=2, sleep=lambda s: None)
        b0 = obs.counter("serving.bisections").value
        ok, failed = d(reqs)
        assert (ok, failed) == (7, 1)
        for r in reqs:
            if r is poison:
                with pytest.raises(ValueError, match="poison"):
                    r.result(timeout=0)
            else:
                assert r.result(timeout=0) == ["ok"]
        assert obs.counter("serving.bisections").value > b0
        # fatal errors are NOT retried: no attempt list repeats itself
        assert len(exe.calls) == len({tuple(c) for c in exe.calls})

    def test_persistent_transient_exhausts_then_bisects_to_leaves(self):
        exe = _ScriptedExecute(transient_failures=10 ** 6)
        d = ResilientDispatcher(exe, max_retries=1, sleep=lambda s: None)
        reqs = [_req(), _req()]
        ok, failed = d(reqs)
        assert (ok, failed) == (0, 2)
        for r in reqs:
            with pytest.raises(faults.FaultInjected):
                r.result(timeout=0)

    def test_breaker_fed_fatal_only_when_no_request_survives(self):
        class FakeBreaker:
            def __init__(self):
                self.events = []

            def record_success(self):
                self.events.append("ok")

            def record_fatal(self):
                self.events.append("fatal")

        fb = FakeBreaker()
        reqs = [_req() for _ in range(4)]
        exe = _ScriptedExecute(poison=[id(reqs[0])])
        ResilientDispatcher(exe, breaker=fb, sleep=lambda s: None)(reqs)
        assert fb.events == ["ok"]  # 3 survivors -> success outcome
        reqs2 = [_req()]
        exe2 = _ScriptedExecute(poison=[id(reqs2[0])])
        ResilientDispatcher(exe2, breaker=fb, sleep=lambda s: None)(reqs2)
        assert fb.events == ["ok", "fatal"]


# -- batcher stop/death semantics (satellite fix) ----------------------------

class TestBatcherStop:
    def test_stop_with_never_started_worker_fails_leftovers(self):
        q = serving.RequestQueue(capacity=8)
        b = DynamicBatcher(q, lambda reqs: None, 4, 0.0)
        futs = [q.put(_req()) for _ in range(3)]
        q.close()
        assert b.stop(drain=True, timeout=1.0)
        for f in futs:
            with pytest.raises(serving.ServingClosed):
                f.result(timeout=0)  # failed fast, not hanging
        assert q.depth() == 0

    def test_stop_join_timeout_on_wedged_worker_fails_leftovers(self):
        q = serving.RequestQueue(capacity=8)
        release = threading.Event()

        def wedge(reqs):
            release.wait(10)
            for r in reqs:
                r.complete(["late"])

        b = DynamicBatcher(q, wedge, 1, 0.0).start()
        first = q.put(_req())   # wedges the worker
        time.sleep(0.05)
        leftovers = [q.put(_req()) for _ in range(3)]
        q.close()
        assert not b.stop(drain=True, timeout=0.1)  # join times out
        for f in leftovers:
            with pytest.raises(serving.ServingClosed):
                f.result(timeout=0)
        release.set()
        assert first.result(timeout=5) == ["late"]  # in-flight finishes
        # drained leftovers were marked done: the completion watermark
        # covers them, so a later swap/wait_for drain can't stall
        assert b.wait_for(leftovers[-1].seq, timeout=5)
        b.stop(timeout=5)

    def test_drain_remaining_on_fail_advances_watermark(self):
        # the supervisor's give-up fail_pending path: requests failed
        # via drain_remaining must advance the batcher watermark or a
        # revived engine's swap drain stalls on them forever
        q = serving.RequestQueue(capacity=8)
        b = DynamicBatcher(q, lambda reqs: None, 4, 0.0)
        futs = [q.put(_req()) for _ in range(5)]
        q.drain_remaining(lambda r: serving.ServingDegraded("gone"),
                          on_fail=lambda r: b._mark_done([r]))
        assert b.completed_seq == futs[-1].seq
        assert b.wait_for(futs[-1].seq, timeout=0)

    def test_worker_death_fails_inflight_batch(self):
        q = serving.RequestQueue(capacity=8)

        def die(reqs):
            raise faults.WorkerKilled("chaos")

        b = DynamicBatcher(q, die, 4, 0.0).start()
        d0 = obs.counter("serving.worker_deaths").value
        fut = q.put(_req())
        with pytest.raises(serving.ServingDegraded, match="died"):
            fut.result(timeout=5)
        for _ in range(100):
            if not b.alive:
                break
            time.sleep(0.01)
        assert not b.alive
        assert obs.counter("serving.worker_deaths").value == d0 + 1

    def test_restart_rearms_dead_worker_preserving_watermark(self):
        q = serving.RequestQueue(capacity=8)
        calls = [0]

        def exe(reqs):
            calls[0] += 1
            if calls[0] == 1:
                raise faults.WorkerKilled("chaos")
            for r in reqs:
                r.complete(["ok"])

        b = DynamicBatcher(q, exe, 4, 0.0).start()
        f1 = q.put(_req())
        with pytest.raises(serving.ServingDegraded):
            f1.result(timeout=5)
        for _ in range(100):
            if not b.alive:
                break
            time.sleep(0.01)
        assert b.restart()
        f2 = q.put(_req())
        assert f2.result(timeout=5) == ["ok"]
        # the death-failed seq was marked done: the watermark moved past it
        assert b.wait_for(f2.seq, timeout=5)
        b.stop(timeout=5)

    def test_stop_no_drain_exits_after_inflight_batch(self):
        q = serving.RequestQueue(capacity=64)
        started = threading.Event()
        release = threading.Event()
        served = [0]

        def exe(reqs):
            started.set()
            release.wait(10)
            served[0] += len(reqs)
            for r in reqs:
                r.complete(["ok"])

        b = DynamicBatcher(q, exe, 1, 0.0).start()
        first = q.put(_req())
        assert started.wait(5)
        backlog = [q.put(_req()) for _ in range(20)]
        q.close()
        stopper = threading.Thread(
            target=b.stop, kwargs={"drain": False, "timeout": 5.0})
        stopper.start()
        time.sleep(0.05)
        release.set()
        stopper.join(10)
        assert first.result(timeout=5) == ["ok"]  # in-flight finished
        for f in backlog:  # backlog FAILED fast, not served
            with pytest.raises(serving.ServingClosed):
                f.result(timeout=5)
        assert served[0] == 1

    def test_out_of_order_completion_watermark_exact(self):
        q = serving.RequestQueue(capacity=8)
        b = DynamicBatcher(q, lambda reqs: None, 4, 0.0)
        r1, r2, r3 = _req(), _req(), _req()
        for r, s in ((r1, 1), (r2, 2), (r3, 3)):
            r.seq = s
        b._mark_done([r3])           # priority lanes complete out of order
        assert b.completed_seq == 0  # seq 1 and 2 still outstanding
        assert not b.wait_for(3, timeout=0.01)
        b._mark_done([r1])
        assert b.completed_seq == 1
        b._mark_done([r2])
        assert b.completed_seq == 3  # contiguous prefix caught up
        assert b.wait_for(3, timeout=0.01)


# -- worker supervisor -------------------------------------------------------

class TestWorkerSupervisor:
    def test_restarts_dead_worker_and_counts(self):
        alive = [False]
        restarted = []
        sup = WorkerSupervisor(interval_s=0.01, max_restarts=3)
        sup.watch("w", should_run=lambda: True,
                  is_alive=lambda: alive[0],
                  restart=lambda: (restarted.append(1),
                                   alive.__setitem__(0, True))[0] or True,
                  fail_pending=lambda: None)
        c0 = obs.counter("serving.worker_restarts").value
        sup.start()
        try:
            for _ in range(200):
                if restarted:
                    break
                time.sleep(0.01)
            assert restarted and alive[0]
            assert obs.counter("serving.worker_restarts").value == c0 + 1
            assert sup.stats()["w"]["restarts"] == 1
        finally:
            sup.stop()
        assert not sup.alive

    def test_give_up_past_budget_fails_pending_and_notifies(self):
        failed, gave = [], []
        sup = WorkerSupervisor(interval_s=0.01, max_restarts=1,
                               on_give_up=lambda name: gave.append(name))
        sup.watch("w", should_run=lambda: True,
                  is_alive=lambda: False,       # restart never sticks
                  restart=lambda: True,
                  fail_pending=lambda: failed.append(1))
        sup.start()
        try:
            for _ in range(300):
                if gave:
                    break
                time.sleep(0.01)
            assert gave == ["w"]
            assert failed                      # pending failed fast
            assert sup.stats()["w"]["gave_up"]
        finally:
            sup.stop()


# -- chaos injectors ---------------------------------------------------------

class TestChaosInjectors:
    def test_flaky_execute_fires_and_restores(self):
        from paddle_tpu import resilience

        assert resilience._serve_fault is None
        with faults.flaky_execute(times=2) as fired:
            hook = resilience._serve_fault
            with pytest.raises(faults.FaultInjected):
                hook([_req()])
            with pytest.raises(faults.FaultInjected):
                hook([_req()])
            hook([_req()])  # budget spent: passes
            assert fired[0] == 2
        assert resilience._serve_fault is None

    def test_injectors_compose_and_unwind(self):
        from paddle_tpu import resilience

        poison = _req()
        poison.seq = 99
        clean = _req()
        clean.seq = 1
        with faults.flaky_execute(times=1):
            with faults.poison_request(99):
                hook = resilience._serve_fault
                with pytest.raises(faults.FaultInjected):
                    hook([clean])              # flaky fires first
                with pytest.raises(ValueError, match="poison"):
                    hook([clean, poison])      # then poison matches
                hook([clean])                  # innocents pass
            assert resilience._serve_fault is not None
        assert resilience._serve_fault is None

    def test_slow_execute_delays(self):
        from paddle_tpu import resilience

        with faults.slow_execute(0.05, times=1) as fired:
            t0 = time.perf_counter()
            resilience._serve_fault([_req()])
            assert time.perf_counter() - t0 >= 0.05
            t0 = time.perf_counter()
            resilience._serve_fault([_req()])  # budget spent
            assert time.perf_counter() - t0 < 0.05
            assert fired[0] == 1


# -- engine integration ------------------------------------------------------

class TestEngineResilience:
    def test_flaky_execute_retries_to_success_bitwise(self, model_dir):
        X = np.random.RandomState(3).randn(2, 8).astype("float32")
        with serving.InferenceEngine(model_dir, batch_buckets=BUCKETS,
                                     supervise=False) as eng:
            want = eng.predict({"x": X})[0]
            r0 = obs.counter("serving.retries").value
            with faults.flaky_execute(times=2):
                got = eng.predict({"x": X}, timeout=30)[0]
            assert got.tobytes() == want.tobytes()
            assert obs.counter("serving.retries").value == r0 + 2

    def test_poison_bisection_on_engine(self, model_dir):
        rng = np.random.RandomState(4)
        payloads = [rng.randn(1, 8).astype("float32") for _ in range(6)]
        eng = serving.InferenceEngine(model_dir, batch_buckets=BUCKETS,
                                      max_batch_size=4, autostart=False,
                                      supervise=False)
        try:
            want = []
            futs = [eng.predict_async({"x": p}) for p in payloads]
            poison_seq = futs[2].seq
            b0 = obs.counter("serving.bisections").value
            with faults.poison_request(poison_seq):
                eng.start()
                for i, f in enumerate(futs):
                    if f.seq == poison_seq:
                        with pytest.raises(ValueError, match="poison"):
                            f.result(timeout=30)
                    else:
                        out = f.result(timeout=30)[0]
                        want.append((i, out))
            assert obs.counter("serving.bisections").value > b0
            # innocents got REAL answers, bitwise equal to a clean engine
            with serving.InferenceEngine(model_dir, batch_buckets=BUCKETS,
                                         supervise=False) as ref:
                for i, out in want:
                    clean = ref.predict({"x": payloads[i]})[0]
                    assert out.tobytes() == clean.tobytes()
        finally:
            eng.stop()

    def test_breaker_degrades_engine_and_half_open_recovers(self, model_dir):
        X = np.zeros((1, 8), "float32")
        with serving.InferenceEngine(
                model_dir, batch_buckets=BUCKETS, supervise=False,
                breaker_threshold=2, breaker_cooldown_s=0.2) as eng:
            with faults.poison_request(lambda r: True):
                for _ in range(2):
                    with pytest.raises(ValueError):
                        eng.predict({"x": X}, timeout=30)
                assert eng.state == "degraded" and not eng.ready()
                assert eng.health()["breaker"] == "open"
                with pytest.raises(serving.ServingDegraded):
                    eng.predict({"x": X})
            time.sleep(0.25)  # cooldown -> half-open probe allowed
            out = eng.predict({"x": X}, timeout=30)
            assert out[0].shape == (1, 4)
            assert eng.state == "ready" and eng.ready()
            assert eng.health()["breaker"] == "closed"

    def test_kill_worker_supervisor_restarts_and_serves(self, model_dir):
        X = np.random.RandomState(5).randn(1, 8).astype("float32")
        with serving.InferenceEngine(
                model_dir, batch_buckets=BUCKETS,
                supervisor_interval_s=0.02) as eng:
            want = eng.predict({"x": X})[0]
            r0 = obs.counter("serving.worker_restarts").value
            with faults.kill_worker(at_dispatch=0):
                doomed = eng.predict_async({"x": X})
                with pytest.raises(serving.ServingDegraded):
                    doomed.result(timeout=10)
            # supervisor notices the dead thread and re-arms it.  Wait
            # on the restart COUNTER: right after result() raises, the
            # dying thread can still be briefly alive, so worker_alive
            # alone can read True before the restart happened.
            deadline = time.time() + 10
            while (time.time() < deadline
                   and obs.counter("serving.worker_restarts").value == r0):
                time.sleep(0.02)
            assert obs.counter("serving.worker_restarts").value == r0 + 1
            assert eng.health()["worker_alive"]
            got = eng.predict({"x": X}, timeout=30)[0]
            assert got.tobytes() == want.tobytes()
            assert eng.health()["workers"]["batcher"]["restarts"] == 1

    def test_explicit_start_revives_given_up_worker(self, model_dir):
        X = np.random.RandomState(6).randn(1, 8).astype("float32")
        eng = serving.InferenceEngine(model_dir, batch_buckets=BUCKETS,
                                      supervisor_interval_s=0.02,
                                      worker_max_restarts=0)
        try:
            want = eng.predict({"x": X})[0]
            with faults.kill_worker(at_dispatch=0):
                with pytest.raises(serving.ServingDegraded):
                    eng.predict({"x": X}, timeout=30)
            # zero restart budget: the supervisor gives up immediately
            # and admission fast-fails
            deadline = time.time() + 10
            while eng.state != "degraded" and time.time() < deadline:
                time.sleep(0.01)
            assert eng.state == "degraded"
            with pytest.raises(serving.ServingDegraded):
                eng.predict({"x": X})
            # an explicit operator start() grants a fresh budget: the
            # worker revives AND admissions stop fast-failing (a revive
            # that left _failed_workers set would serve nobody forever)
            eng.start()
            assert eng.health()["worker_alive"]
            assert eng.state == "ready"
            got = eng.predict({"x": X}, timeout=30)[0]
            assert got.tobytes() == want.tobytes()
            assert eng.health()["workers"]["batcher"]["gave_up"] is False
        finally:
            eng.stop()

    def test_priority_kwarg_flows_to_queue(self, model_dir):
        X = np.zeros((1, 8), "float32")
        eng = serving.InferenceEngine(model_dir, batch_buckets=BUCKETS,
                                      autostart=False, supervise=False)
        try:
            f = eng.predict_async({"x": X}, priority="interactive")
            assert f.priority == "interactive"
            assert eng.health()["class_depths"]["interactive"] == 1
            with pytest.raises(serving.ServingError, match="priority"):
                eng.predict_async({"x": X}, priority="nope")
        finally:
            eng.stop()

    def test_admission_shed_after_estimator_warm(self, model_dir):
        X = np.zeros((1, 8), "float32")
        eng = serving.InferenceEngine(model_dir, batch_buckets=BUCKETS,
                                      autostart=False, supervise=False)
        try:
            # no worker running: queue state is fully deterministic.
            # Warm the estimator to 10 rows/s, queue 5 rows ahead ->
            # ~500ms estimated wait for a batch-class arrival.
            eng._queue.note_service(rows=10, seconds=1.0)
            assert eng.health()["service_rate_rows_per_s"] == 10.0
            futs = [eng.predict_async({"x": X}) for _ in range(5)]
            s0 = obs.counter("serving.shed_admission").value
            with pytest.raises(serving.ServingOverloaded):
                eng.predict_async({"x": X}, deadline_ms=1)
            assert obs.counter("serving.shed_admission").value == s0 + 1
            # a deadline beyond the estimate is admitted fine
            ok = eng.predict_async({"x": X}, deadline_ms=5000)
            # and an INTERACTIVE request sees no same-or-higher backlog
            # (all 6 queued rows are batch-class), so even 1ms admits
            fast = eng.predict_async({"x": X}, deadline_ms=25,
                                     priority="interactive")
            eng.start()
            assert ok.result(timeout=30) and fast.result(timeout=30)
            for f in futs:
                f.result(timeout=30)
        finally:
            eng.stop()


# -- decode: mid-decode deadline shed detail (satellite) ---------------------

@functools.lru_cache(maxsize=None)
def _decode_model():
    """One model object for the module: its schedulers dispatch the model's
    own step programs, so a shape is traced once."""
    pytest.importorskip("jax")
    from paddle_tpu.models import transformer as T

    params, meta = T.lm_params(seed=7, vocab_size=50, n_layer=2,
                               n_head=2, d_model=32, d_inner=64,
                               max_length=128)
    return T.build_decode_model(params, meta)


def _decode_scheduler(max_new_tokens=40):
    cfg = serving.DecodeConfig(num_slots=2, page_size=8, max_seq_len=64,
                               max_new_tokens=max_new_tokens)
    return serving.DecodeScheduler(_decode_model(), cfg, autostart=False)


class TestDecodeMidDecodeShed:
    def test_mid_decode_expiry_message_and_counter(self):
        sched = _decode_scheduler()
        mid0 = obs.counter("serving.decode.expired_mid_decode").value
        try:
            prompt = np.arange(1, 9, dtype=np.int32)
            with faults.slow_execute(0.05):
                fut = sched.submit(prompt, max_new_tokens=40,
                                   deadline_ms=250)
                sched.start()
                # poll done() instead of result(): the client-side
                # deadline in result() fires at the same instant the
                # worker sheds, and the worker can be one slow
                # iteration late
                deadline = time.time() + 30
                while not fut.done() and time.time() < deadline:
                    time.sleep(0.01)
            assert fut.done()
            with pytest.raises(serving.ServingTimeout) as ei:
                fut.result(timeout=0)
            msg = str(ei.value)
            assert "mid-decode" in msg
            assert "in queue" in msg and "decoding" in msg
            assert "-0." not in msg
            assert (obs.counter("serving.decode.expired_mid_decode").value
                    == mid0 + 1)
        finally:
            sched.stop(timeout=10)

    def test_decode_admission_shed_with_warm_estimator(self):
        sched = _decode_scheduler(max_new_tokens=4)
        prompt = np.arange(1, 9, dtype=np.int32)
        try:
            # worker not started: deterministic backlog.  Warm the EMA
            # to 10 sequences/s, queue 5 ahead -> ~500ms estimated wait
            sched._queue.note_service(rows=10, seconds=1.0)
            backlog = [sched.submit(prompt) for _ in range(5)]
            s0 = obs.counter("serving.decode.shed_admission").value
            with pytest.raises(serving.ServingOverloaded):
                sched.submit(prompt, deadline_ms=5)
            assert (obs.counter("serving.decode.shed_admission").value
                    == s0 + 1)
            sched.start()
            for f in backlog:
                assert f.result(timeout=30) is not None
            # a real serve run feeds the EMA from retirement throughput
            assert sched._queue.service_rate is not None
        finally:
            sched.stop(timeout=10)

    def test_queue_expiry_sheds_do_not_inflate_decode_service_rate(self):
        sched = _decode_scheduler(max_new_tokens=4)
        prompt = np.arange(1, 9, dtype=np.int32)
        try:
            # queue several requests whose deadlines are already dead:
            # the worker sheds them at ~zero cost in _admit
            doomed = [sched.submit(prompt, deadline_ms=1) for _ in range(6)]
            time.sleep(0.05)
            sched.start()
            for f in doomed:
                end = time.time() + 10
                while not f.done() and time.time() < end:
                    time.sleep(0.01)
                assert f.done()
            # zero-cost sheds must NOT have fed the service-rate EMA
            # (an inflated rate would disable shed-at-admission under
            # exactly the overload it exists for)
            assert sched._queue.service_rate is None
            # a REAL served sequence does feed it (poll: the client
            # wakes on complete() just before the worker notes the rate)
            assert sched.generate(prompt, timeout=30) is not None
            end = time.time() + 10
            while sched._queue.service_rate is None and time.time() < end:
                time.sleep(0.01)
            assert sched._queue.service_rate is not None
        finally:
            sched.stop(timeout=10)

    def test_dual_path_engine_stays_ready_when_breaker_open(self, tmp_path):
        model_dir = _save_model(str(tmp_path / "m"))
        eng = serving.InferenceEngine(
            model_dir, batch_buckets=BUCKETS,
            decode_model=_decode_model(),
            decode_config=serving.DecodeConfig(
                num_slots=2, page_size=8, max_seq_len=64,
                max_new_tokens=4),
            supervise=False, breaker_threshold=1, breaker_cooldown_s=60.0)
        try:
            X = np.zeros((1, 8), "float32")
            with faults.poison_request(
                    lambda r: not isinstance(r,
                                             serving.GenerateRequest)):
                with pytest.raises(ValueError):
                    eng.predict({"x": X}, timeout=30)
            assert eng.state == "degraded"
            with pytest.raises(serving.ServingDegraded):
                eng.predict_async({"x": X})
            # ...but the DECODE path is healthy: engine stays ready and
            # generate() serves normally while predict is broken
            assert eng.ready()
            toks = eng.generate(np.arange(1, 9, dtype=np.int32),
                                timeout=30)
            assert len(toks) == 4
        finally:
            eng.stop()

    def test_decode_stop_no_drain_fails_actives_after_iteration(self):
        sched = _decode_scheduler(max_new_tokens=40)
        prompt = np.arange(1, 9, dtype=np.int32)
        with faults.slow_execute(0.05):
            f1 = sched.submit(prompt)
            f2 = sched.submit(prompt)
            sched.start()
            deadline = time.time() + 10
            while (sched.stats()["active"] < 2
                   and time.time() < deadline):
                time.sleep(0.01)
            assert sched.stats()["active"] == 2
            # non-drain stop must FAIL the actives after the in-flight
            # iteration, not decode 40 tokens per sequence to completion
            assert sched.stop(drain=False, timeout=10)
        for f in (f1, f2):
            with pytest.raises(serving.ServingClosed):
                f.result(timeout=0)
        assert sched.stats()["active"] == 0
        assert sched.stats()["kv_pages_used"] == 0

    def test_stop_join_timeout_on_wedged_decode_worker_fails_queued(self):
        sched = _decode_scheduler(max_new_tokens=4)
        prompt = np.arange(1, 9, dtype=np.int32)
        with faults.slow_execute(1.0):
            f1 = sched.submit(prompt)
            sched.start()
            time.sleep(0.2)              # worker wedged in the dispatch
            f2 = sched.submit(prompt)    # queued behind the wedge
            assert not sched.stop(drain=True, timeout=0.2)  # join timeout
            with pytest.raises(serving.ServingClosed):
                f2.result(timeout=0)     # failed fast, not hanging
        # once the wedge clears the worker finishes the in-flight
        # sequence (drain) and exits
        assert f1.result(timeout=30) is not None
        for _ in range(200):
            if not sched.alive:
                break
            time.sleep(0.05)
        assert not sched.alive


# -- a fault with a decode step in flight (ISSUE 36) --------------------------

class TestFaultWithAStepInFlight:
    """The loop dispatches step n+1 before it reads step n: a fault can
    land on either, and both ways the served tokens are a clean run's."""

    PROMPT = np.arange(1, 9, dtype=np.int32)
    KW = dict(max_new_tokens=24, temperature=0.7, seed=11)

    def _clean(self):
        sched = _decode_scheduler()
        sched.start()
        try:
            return sched.generate(self.PROMPT, timeout=120, **self.KW)
        finally:
            sched.stop(timeout=10)

    @pytest.mark.parametrize("where", ["dispatch", "readback"])
    def test_a_transient_fault_retries_to_a_clean_runs_tokens(self, where):
        want = self._clean()
        sched = _decode_scheduler()
        r0 = obs.counter("serving.decode.step_retries").value
        d0 = obs.counter("serving.decode.tokens_discarded").value
        fired = [0]
        if where == "readback":
            # the readback of step n, with step n+1 already dispatched
            # behind it: both are dropped, the cache is what step n took
            read = sched._read_step

            def lossy(sent):
                if (not fired[0] and len(sched._unread) == 2
                        and len(sent.entries[0][1].generated) >= 5):
                    fired[0] += 1
                    raise faults.FaultInjected("injected lost readback")
                return read(sent)

            sched._read_step = lossy
        sched.start()
        try:
            if where == "dispatch":
                # the dispatch of step n+1 while step n is unread
                mid_run = lambda rs: (  # noqa: E731
                    len(rs[0].journal.accepted) >= 5 and bool(sched._unread))
                with faults.flaky_execute(times=1, match=mid_run) as hit:
                    got = sched.generate(self.PROMPT, timeout=120, **self.KW)
                fired = hit
            else:
                got = sched.generate(self.PROMPT, timeout=120, **self.KW)
            assert sched.stats()["kv_pages_used"] == 0
        finally:
            sched.stop(timeout=10)
        assert fired[0] == 1
        assert obs.counter("serving.decode.step_retries").value == r0 + 1
        assert got.tobytes() == want.tobytes()
        # a dropped step is computed again, not served twice or discarded
        assert obs.counter("serving.decode.tokens_discarded").value == d0

    @pytest.mark.parametrize("where", ["dispatch", "readback"])
    def test_a_fatal_fault_fails_the_actives_and_the_next_request_is_served(
            self, where):
        """As on the chip, where the failed dispatch has consumed the
        donated pools: the actives fail typed, the pools come back zeroed
        and the next request is served a clean run's tokens."""
        want = self._clean()
        sched = _decode_scheduler()
        sched._donated = True            # the host's side of donation
        fired = [0]
        if where == "readback":
            read = sched._read_step

            def broken(sent):
                if not fired[0] and len(sched._unread) == 2:
                    fired[0] += 1
                    raise ValueError("injected fatal readback")
                return read(sent)

            sched._read_step = broken
        sched.start()
        try:
            fut = sched.submit(self.PROMPT, **self.KW)
            other = sched.submit(self.PROMPT[:5], **self.KW)
            if where == "dispatch":
                fatal = lambda rs: ValueError("injected fatal dispatch")  # noqa
                mid_run = lambda rs: (  # noqa: E731
                    len(rs[0].journal.accepted) >= 3 and bool(sched._unread))
                with faults.flaky_execute(times=1, match=mid_run,
                                          exc_factory=fatal) as fired:
                    for f in (fut, other):
                        with pytest.raises(ValueError, match="injected"):
                            f.result(timeout=120)
            else:
                for f in (fut, other):
                    with pytest.raises(ValueError, match="injected"):
                        f.result(timeout=120)
            assert fired[0] == 1
            # the futures fail first, the pools are zeroed behind them
            deadline = time.time() + 10
            while (np.asarray(sched._cache.k_pool).any()
                   and time.time() < deadline):
                time.sleep(0.01)
            assert not np.asarray(sched._cache.k_pool).any()
            assert not sched._unread
            st = sched.stats()
            assert st["active"] == 0 and st["kv_pages_used"] == 0
            got = sched.generate(self.PROMPT, timeout=120, **self.KW)
        finally:
            sched.stop(timeout=10)
        assert got.tobytes() == want.tobytes()

    def test_a_readback_lost_past_a_chunks_write_is_not_retried(self):
        """A prefill chunk wrote the cache behind the unread step: there is
        nothing to roll back to, so the lost step is not retried.  The
        decoding sequences fail typed; the one whose chunk it was still
        prefills (its token is read behind that step), so it and the next
        request are served a clean run's tokens from the same pools."""
        want = self._clean()
        sched = _decode_scheduler()
        fired = [0]
        read = sched._read_step

        def lossy(sent):
            if not fired[0] and sent.pools_before is None:
                fired[0] += 1
                raise faults.FaultInjected("injected lost readback")
            return read(sent)

        sched._read_step = lossy
        r0 = obs.counter("serving.decode.step_retries").value
        sched.start()
        try:
            first = sched.submit(self.PROMPT[:5], **self.KW)
            while len(first.token_times) < 3:
                time.sleep(0.002)
            second = sched.submit(self.PROMPT, **self.KW)
            with pytest.raises(serving.ServingDegraded, match="moved on"):
                first.result(timeout=120)
            assert second.result(timeout=120).tobytes() == want.tobytes()
            assert not any(s is not None and s.inflight
                           for s in sched._slots)
            got = sched.generate(self.PROMPT, timeout=120, **self.KW)
            assert sched.stats()["kv_pages_used"] == 0
        finally:
            sched.stop(timeout=10)
        assert fired[0] == 1
        assert obs.counter("serving.decode.step_retries").value == r0
        assert got.tobytes() == want.tobytes()
