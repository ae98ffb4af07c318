"""Observability subsystem: registry semantics, sink behavior, profiler
rebase, counter-view contracts, and telemetry-neutral execution.

The heavyweight end-to-end assertions (JSONL schema over a real training
run, Perfetto trace overlap, bitwise neutrality with checkpoints +
nan_guard) live in tools/check_observability.py, wired into tier-1 via
test_observability_gate.py; this file covers the unit surface.
"""
import json
import threading

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_counter_gauge_timer_basics():
    tel = obs.Telemetry(enabled=True)
    c = tel.counter("c")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert tel.counter("c") is c  # one cell per name
    g = tel.gauge("g")
    assert g.value is None
    g.set(3.5)
    assert g.value == 3.5
    t = tel.timer("t")
    t.observe(0.25)
    with t.time():
        pass
    calls, total, avg, mn, mx = t.stats()
    assert calls == 2 and total >= 0.25 and mx == 0.25 and mn >= 0.0
    assert avg == pytest.approx(total / 2)


def test_reset_zeroes_in_place_and_respects_prefix():
    tel = obs.Telemetry(enabled=True)
    a = tel.counter("ns.a")
    b = tel.counter("other.b")
    tm = tel.timer("ns.t")
    a.inc(3)
    b.inc(7)
    tm.observe(1.0)
    tel.reset("ns.")
    # zeroed IN PLACE: cached handles and fresh lookups agree
    assert a.value == 0 and tel.counter("ns.a") is a
    assert tm.stats() is None
    assert b.value == 7  # outside the prefix: untouched
    tel.reset()
    assert b.value == 0


def test_counter_thread_safety():
    tel = obs.Telemetry(enabled=True)
    c = tel.counter("threads")
    n, per = 8, 5000

    def worker():
        for _ in range(per):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n * per


def test_env_killswitch(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY", "0")
    tel = obs.Telemetry()
    assert not tel.enabled
    sink = obs.RingBufferSink()
    tel.add_sink(sink)
    assert not tel.recording  # disabled wins over attached sinks
    tel.emit({"type": "step"})
    assert sink.records == []
    with tel.span("x"):     # killswitch: sinks quiet, the cell still counts
        pass
    assert sink.spans == [] and tel.histogram("x").count == 1
    monkeypatch.delenv("PADDLE_TPU_TELEMETRY")
    assert tel.configure() is True  # re-reads the env
    assert tel.recording


def test_counters_count_even_when_disabled():
    tel = obs.Telemetry(enabled=False)
    c = tel.counter("always")
    c.inc(2)
    assert c.value == 2  # the bitwise on/off contract for accessor views


def test_spans_only_flow_to_span_sinks():
    tel = obs.Telemetry(enabled=True)
    with tel.span("x"):     # no sink: the cell alone
        pass
    assert tel.histogram("x").count == 1
    ring = obs.RingBufferSink(record_spans=True)
    tel.add_sink(ring)
    with tel.span("hello", k="v"):
        pass
    tel.record_span("manual", 123.0, 0.5, {"a": 1})
    spans = ring.spans
    assert [s["name"] for s in spans] == ["hello", "manual"]
    assert spans[0]["tags"] == {"k": "v"}
    assert spans[1]["dur"] == 0.5
    tel.remove_sink(ring)
    with tel.span("x"):
        pass
    assert [s["name"] for s in ring.spans] == ["hello", "manual"]


def test_broken_sink_never_raises_into_the_loop():
    class Exploding(obs.Sink):
        def emit(self, record):
            raise RuntimeError("boom")

    tel = obs.Telemetry(enabled=True)
    ring = obs.RingBufferSink()
    tel.add_sink(Exploding())
    tel.add_sink(ring)
    tel.emit({"type": "step"})  # must not raise
    assert len(ring.records) == 1  # later sinks still served


# ---------------------------------------------------------------------------
# sinks
# ---------------------------------------------------------------------------


def test_jsonl_sink_coerces_non_json_values(tmp_path):
    path = str(tmp_path / "t.jsonl")
    sink = obs.JsonlSink(path)
    sink.emit({"a": np.float32(1.5), "b": np.int64(3), "c": "x"})
    sink.close()
    (rec,) = [json.loads(line) for line in open(path)]
    assert rec == {"a": 1.5, "b": 3.0, "c": "x"}


def test_ring_buffer_sink_bounded():
    sink = obs.RingBufferSink(capacity=3)
    for i in range(10):
        sink.emit({"i": i})
    assert [r["i"] for r in sink.records] == [7, 8, 9]


def test_stdout_summary_sink_every_n():
    import io

    stream = io.StringIO()
    sink = obs.StdoutSummarySink(every_n=2, stream=stream)
    rec = {"type": "step", "source": "trainer", "step": 0,
           "steps_per_s": 100.0, "feed_host_copies": 1,
           "prefetch_transfers": 2, "nan_ok": True}
    sink.emit(dict(rec))
    assert stream.getvalue() == ""  # below the window
    sink.emit(dict(rec, step=1, steps_per_s=300.0))
    out = stream.getvalue()
    assert "200.0 steps/s (n=2)" in out and "nan_ok=True" in out


def test_chrome_trace_sink_structure(tmp_path):
    path = str(tmp_path / "trace.json")
    sink = obs.ChromeTraceSink(path)
    sink.emit_span("work", 100.0, 0.002, threading.current_thread(), {"k": 1})
    sink.emit({"type": "step", "source": "trainer", "step": 0,
               "ts": 100.002, "steps_per_s": 10.0})
    sink.close()
    trace = json.load(open(path))
    events = trace["traceEvents"]
    phases = sorted(e["ph"] for e in events)
    assert phases == ["M", "X", "i"]  # thread_name + span + step instant
    (span,) = [e for e in events if e["ph"] == "X"]
    assert span["name"] == "work" and span["dur"] == pytest.approx(2000.0)
    assert span["ts"] == pytest.approx(100.0 * 1e6)


def test_stdout_summary_sink_concurrent_rollover_loses_nothing():
    """8 threads hammering every_n-windowed emit: rollovers race, but
    every record lands in exactly one flushed window (the per-window
    ``(n=K)`` counts must sum to the total emitted) and no line is
    interleaved mid-write."""
    import io

    stream = io.StringIO()
    sink = obs.StdoutSummarySink(every_n=5, stream=stream)
    per_thread, n_threads = 250, 8

    def work(tid):
        for i in range(per_thread):
            sink.emit({"type": "step", "source": "t%d" % tid, "step": i,
                       "steps_per_s": 100.0, "feed_host_copies": 0,
                       "prefetch_transfers": 0})

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sink.flush()   # drain the final partial window
    lines = stream.getvalue().splitlines()
    counted = 0
    for line in lines:
        assert line.startswith("[telemetry] ")     # no torn interleaving
        assert "steps/s (n=" in line
        counted += int(line.split("(n=")[1].split(")")[0])
    assert counted == per_thread * n_threads
    sink.flush()   # empty window: no extra output
    assert stream.getvalue().splitlines() == lines


def test_chrome_trace_sink_concurrent_thread_metadata(tmp_path):
    """Spans emitted from 6 racing threads: the trace must contain
    exactly one thread_name metadata event per emitting thread, unique
    tids, and every span filed under ITS OWN thread's tid — per-thread
    attribution must survive the tid-allocation race."""
    path = str(tmp_path / "trace.json")
    sink = obs.ChromeTraceSink(path)
    per_thread, n_threads = 200, 6

    def work(tid):
        me = threading.current_thread()
        for i in range(per_thread):
            sink.emit_span("op-%d" % tid, 100.0 + i * 1e-4, 1e-5, me,
                           {"thread_tag": tid})

    threads = [threading.Thread(target=work, args=(t,),
                                name="emitter-%d" % t)
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sink.close()
    events = json.load(open(path))["traceEvents"]
    metas = [e for e in events if e["ph"] == "M"]
    spans = [e for e in events if e["ph"] == "X"]
    assert len(spans) == per_thread * n_threads      # nothing lost
    names = sorted(m["args"]["name"] for m in metas)
    assert names == sorted("emitter-%d" % t for t in range(n_threads))
    tids = [m["tid"] for m in metas]
    assert len(set(tids)) == n_threads               # unique tracks
    tid_by_name = {m["args"]["name"]: m["tid"] for m in metas}
    for span in spans:
        emitter = int(span["args"]["thread_tag"])
        assert span["tid"] == tid_by_name["emitter-%d" % emitter], (
            "span attributed to the wrong thread track")


def test_print_report_respects_killswitch(capsys):
    tel = obs.get_telemetry()
    old = tel.enabled
    try:
        tel.configure(True)
        assert obs.print_report("hello") is True
        assert "hello" in capsys.readouterr().out
        tel.configure(False)
        assert obs.print_report("quiet") is False
        assert capsys.readouterr().out == ""
    finally:
        tel.configure(old)


# ---------------------------------------------------------------------------
# profiler rebase (satellite: global dict state -> registry, quiet mode)
# ---------------------------------------------------------------------------


def test_profiler_sessions_do_not_leak(tmp_path):
    p1 = str(tmp_path / "r1.txt")
    p2 = str(tmp_path / "r2.txt")
    with fluid.profiler.profiler("All", profile_path=p1):
        fluid.profiler.record("evt_one", 0.5)
    with fluid.profiler.profiler("All", profile_path=p2):
        fluid.profiler.record("evt_two", 0.25)
    r1, r2 = open(p1).read(), open(p2).read()
    assert "evt_one" in r1
    # the second session starts a clean window: no leak from the first
    assert "evt_one" not in r2 and "evt_two" in r2


def test_stop_profiler_quiet_under_killswitch(capsys):
    tel = obs.get_telemetry()
    old = tel.enabled
    try:
        tel.configure(False)
        with fluid.profiler.profiler("All"):
            fluid.profiler.record("quiet_evt", 0.1)
        assert capsys.readouterr().out == ""  # no bare print under pytest
        tel.configure(True)
        with fluid.profiler.profiler("All"):
            fluid.profiler.record("loud_evt", 0.1)
        assert "loud_evt" in capsys.readouterr().out
    finally:
        tel.configure(old)


def test_profiler_record_thread_safe():
    fluid.profiler.reset_profiler()

    def worker(i):
        for _ in range(500):
            fluid.profiler.record("mt_evt", 0.001)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tm = obs.get_telemetry().timer(fluid.profiler.TIMING_PREFIX + "mt_evt")
    assert tm.count == 2000
    fluid.profiler.reset_profiler()
    assert tm.stats() is None


def test_record_event_context():
    fluid.profiler.reset_profiler()
    with fluid.profiler.record_event("ctx_evt"):
        pass
    report = fluid.profiler.format_report()
    assert "ctx_evt" in report
    fluid.profiler.reset_profiler()


# ---------------------------------------------------------------------------
# counter views match the legacy accessors bitwise, telemetry on or off
# ---------------------------------------------------------------------------


def _mlp():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[6], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            h = fluid.layers.fc(x, size=8, act="relu")
            pred = fluid.layers.fc(h, size=1)
            loss = fluid.layers.mean(fluid.layers.square(pred - y))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _run_steps(n=4, sinks=()):
    from paddle_tpu.executor import feed_host_copy_count
    from paddle_tpu.reader.device_prefetch import (put_feed_on_device,
                                                   transfer_count)

    main, startup, loss = _mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(4, 6).astype("float32"),
            "y": rng.randn(4, 1).astype("float32")}
    for s in sinks:
        obs.add_sink(s)
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            copies0, transfers0 = feed_host_copy_count(), transfer_count()
            dev_feed = put_feed_on_device(feed, exe, main)
            for _ in range(n):
                out = exe.run(main, feed=dev_feed, fetch_list=[loss])
            host_copies = feed_host_copy_count() - copies0
            transfers = transfer_count() - transfers0
            return host_copies, transfers, np.asarray(out[0]).tobytes()
    finally:
        for s in sinks:
            obs.remove_sink(s)


def test_counter_views_are_registry_cells():
    from paddle_tpu.executor import feed_host_copy_count
    from paddle_tpu.reader.device_prefetch import transfer_count

    tel = obs.get_telemetry()
    before = feed_host_copy_count()
    tel.counter("executor.feed_host_copy").inc(5)
    assert feed_host_copy_count() == before + 5
    before = transfer_count()
    tel.counter("prefetch.transfer").inc(2)
    assert transfer_count() == before + 2


def test_counters_and_loss_identical_telemetry_on_vs_off():
    ring = obs.RingBufferSink(record_spans=True)
    np.random.seed(3)
    on = _run_steps(sinks=[ring])
    np.random.seed(3)
    off = _run_steps(sinks=[])
    # device feeds: zero host copies, one transfer per entry — and the
    # counters (and the loss bytes) must not care whether telemetry ran
    assert on == off
    assert on[0] == 0 and on[1] == 2
    assert ring.records, "sink saw no records while attached"


def test_span_only_sink_sees_dispatch_spans():
    """A wants_spans-only sink (no record sink attached) must still get
    the executor dispatch/compile spans — the trace overlap view cannot
    depend on a record sink also being attached."""

    class SpanOnly(obs.Sink):
        wants_records = False
        wants_spans = True

        def __init__(self):
            self.names = []

        def emit_span(self, name, ts, dur, thread, tags):
            self.names.append(name)

    sink = SpanOnly()
    assert not obs.get_telemetry().recording
    _run_steps(n=3, sinks=[sink])
    assert not obs.get_telemetry().recording  # still no record sink
    assert "executor.dispatch" in sink.names
    assert "executor.compile" in sink.names


def test_executor_step_records_flow_and_tag_fast_path():
    ring = obs.RingBufferSink()
    _run_steps(n=5, sinks=[ring])
    steps = [r for r in ring.records
             if r.get("type") == "step" and r.get("source") == "executor"]
    assert len(steps) >= 5
    for r in steps:
        for k in obs.STEP_SCHEMA["required"]:
            assert k in r, (k, r)
    assert any(r["fast_path"] for r in steps), "fast path never recorded"
    assert any(r.get("compile") for r in steps), "no compile-step record"
    assert len({r["run_id"] for r in steps}) == 1


# ---------------------------------------------------------------------------
# resilience retry telemetry
# ---------------------------------------------------------------------------


def test_retry_counter_and_events():
    from paddle_tpu import resilience

    ring = obs.RingBufferSink()
    obs.add_sink(ring)
    try:
        before = resilience.retry_count()
        calls = [0]

        def flaky():
            calls[0] += 1
            if calls[0] < 3:
                raise OSError("transient hiccup")
            return "ok"

        policy = resilience.RetryPolicy(max_retries=5, base_delay=0.0,
                                        jitter=0.0, sleep=lambda s: None)
        assert resilience.call_with_retry(flaky, policy=policy) == "ok"
        assert resilience.retry_count() - before == 2
        retries = [r for r in ring.records if r.get("type") == "retry"]
        assert len(retries) == 2
        assert all("hiccup" in r["error"] for r in retries)
    finally:
        obs.remove_sink(ring)


# ---------------------------------------------------------------------------
# satellite: compiled_op_report / profile_program coverage
# ---------------------------------------------------------------------------


def test_compiled_op_report_out_bytes_sort():
    from paddle_tpu.jax_bridge import init_state

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[6], dtype="float32")
        h = fluid.layers.fc(x, size=16, act="relu")
        fluid.layers.fc(h, size=2, act="softmax")
    state = init_state(startup)
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(4, 6).astype("float32")}
    report, rows = fluid.profiler.compiled_op_report(
        main, feed, state=state, sorted_key="out_bytes")
    body = report.splitlines()[1:]
    byte_col = [int(ln.split()[-1]) for ln in body]
    assert byte_col == sorted(byte_col, reverse=True)
    assert sum(r["out_bytes"] for r in rows.values()) == sum(byte_col)


def test_profile_program_backward_whole_block_row():
    from paddle_tpu.jax_bridge import init_state

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(x, size=1)
        loss = fluid.layers.mean(fluid.layers.square(pred - y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    state = init_state(startup)
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(2, 4).astype("float32"),
            "y": rng.randn(2, 1).astype("float32")}
    report = fluid.profiler.profile_program(main, feed, state=state, iters=2)
    assert "backward(whole block)" in report
    assert report.splitlines()[0].split()[0] == "Op"
