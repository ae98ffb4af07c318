"""The one span primitive and the phases it times (PR 24): every span
observes into the cell of its name with no sink attached, ``timed`` /
``observe_span`` / ``span`` land in ONE cell, spans lie in a ``jax.profiler``
trace on their thread's line, the scheduler's and the executor's phase means
add up, and none of it changes an output bit.
"""
import gc
import glob
import inspect
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import executor as executor_mod  # noqa: E402
from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu import serving  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402
from paddle_tpu.reader import device_prefetch  # noqa: E402

PREFIX = "paddle_tpu."


# -- the primitive -----------------------------------------------------------

def test_span_observes_into_its_cell_with_no_sink():
    tel = obs.Telemetry(enabled=True)
    assert not tel.span_active() and not tel.recording
    with tel.span("phase.a", k=1) as sp:
        time.sleep(0.002)
    snap = tel.histogram("phase.a").snapshot()
    assert snap.count == 1 and snap.sum == sp.duration >= 0.002
    assert snap.mean == sp.duration


@pytest.mark.parametrize("how", ["span", "timed", "observe_span",
                                 "module.span", "module.timed",
                                 "module.observe_span"])
def test_every_way_to_time_lands_in_the_one_cell(how):
    """No Timer twin: whatever the call site's spelling, the phase is one
    histogram cell and nothing else in the registry."""
    if how.startswith("module."):
        tel, api, name = obs.get_telemetry(), obs, "phase.one_cell." + how
        obs.reset("phase.one_cell.")
    else:
        tel = api = obs.Telemetry(enabled=True)
        name = "phase.one_cell"
    ring = obs.RingBufferSink(record_spans=True)
    tel.add_sink(ring)
    try:
        fn = how.split(".")[-1]
        if fn == "observe_span":
            dur = api.observe_span(name, time.time(), time.perf_counter(),
                                   {"k": 1})
        else:
            with getattr(api, fn)(name, k=1) as sp:
                pass
            dur = sp.duration
    finally:
        tel.remove_sink(ring)
    assert tel.histogram(name).snapshot().sum == dur
    assert tel.histogram(name).count == 1
    assert name not in tel.timers()
    assert [(s["name"], s["tags"]) for s in ring.spans] == [(name, {"k": 1})]


def test_timed_is_span():
    assert obs.Telemetry.timed is obs.Telemetry.span
    assert obs.timed is obs.span


def test_nesting_keeps_parent_at_least_the_sum_of_its_children():
    tel = obs.Telemetry(enabled=True)
    for _ in range(20):
        with tel.span("p"):
            with tel.span("p.c1"):
                time.sleep(0.0005)
            with tel.span("p.c2"):
                with tel.span("p.c2.g"):
                    time.sleep(0.0005)
    s = {n: tel.histogram(n).snapshot() for n in ("p", "p.c1", "p.c2",
                                                  "p.c2.g")}
    assert all(v.count == 20 for v in s.values())
    assert s["p"].sum >= s["p.c1"].sum + s["p.c2"].sum
    assert s["p.c2"].sum >= s["p.c2.g"].sum


@pytest.mark.parametrize("close_as, cells", [
    ("other", {"opened": 0, "other": 1}),
    (None, {"opened": 0}),
    ("opened", {"opened": 1}),
])
def test_a_span_closes_into_the_cell_it_is_named_at_exit(close_as, cells):
    tel = obs.Telemetry(enabled=True)
    ring = obs.RingBufferSink(record_spans=True)
    tel.add_sink(ring)
    with tel.span("opened") as sp:
        sp.name = close_as
    assert sp.duration is not None
    assert {n: tel.histogram(n).count for n in cells} == cells
    assert [s["name"] for s in ring.spans] == [n for n, c in cells.items()
                                               if c]


def test_an_exception_still_closes_the_span():
    tel = obs.Telemetry(enabled=True)
    with pytest.raises(KeyError):
        with tel.span("boom"):
            raise KeyError("x")
    assert tel.histogram("boom").count == 1


def test_killswitch_quiets_sinks_and_records_but_cells_count():
    tel = obs.Telemetry(enabled=False)
    ring = obs.RingBufferSink(record_spans=True)
    tel.add_sink(ring)
    with tel.span("quiet"):
        pass
    tel.observe_span("quiet", time.time(), time.perf_counter())
    tel.record_span("after_the_fact", time.time(), 0.1)
    assert ring.spans == [] and ring.records == []
    assert tel.histogram("quiet").count == 2
    # record_span is for per-request roots: sinks only, never a cell
    assert tel.histogram("after_the_fact").count == 0


def test_spans_from_two_threads_share_the_cell():
    tel = obs.Telemetry(enabled=True)

    def work():
        for _ in range(200):
            with tel.span("mt"):
                pass

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert tel.histogram("mt").count == 800


# -- fixtures for the hot loops ----------------------------------------------

@pytest.fixture(scope="module")
def decode_model():
    params, meta = T.lm_params(seed=7, vocab_size=50, n_layer=2, n_head=2,
                               d_model=32, d_inner=64, max_length=128)
    return T.build_decode_model(params, meta)


def _cfg(**kw):
    base = dict(num_slots=4, page_size=8, max_seq_len=64, max_new_tokens=8,
                prefill_chunk_tokens=16, prefix_cache=True)
    base.update(kw)
    return serving.DecodeConfig(**base)


def _prompts(n, seed=0, lo=3, hi=40):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 50, size=rng.randint(lo, hi)).astype(np.int32)
            for _ in range(n)]


def _train_program():
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 5
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=16, act="relu")
        p = fluid.layers.fc(input=h, size=4, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(input=p, label=y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    rng = np.random.RandomState(1)
    feed = {"x": rng.randn(16, 8).astype(np.float32),
            "y": rng.randint(0, 4, (16, 1)).astype(np.int64)}
    return main, startup, loss, feed


SCHED_CELLS = ("iteration", "iteration.host", "iteration.unspanned",
               "admit", "sweep", "chunk.build",
               "prefill", "prefill.dispatch", "prefill.wait", "chunk.commit",
               "step.build", "step", "step.dispatch", "step.wait",
               "step.commit", "idle")


def _sched_snap():
    return {c: obs.histogram("serving.decode." + c).snapshot()
            for c in SCHED_CELLS}


@pytest.fixture(scope="module")
def generate_run(decode_model):
    """One short CPU ``generate`` burst through a chunked scheduler; the
    phase cells' deltas over it, and the counters' beside them."""
    counters = ("steps", "prefills", "tokens", "requests")
    sched = serving.DecodeScheduler(decode_model, _cfg())
    time.sleep(0.12)            # at least one idle wait before the burst
    c0 = {n: obs.counter("serving.decode." + n).value for n in counters}
    h0 = _sched_snap()
    # a collection between two spans of a turn is a child of the turn too
    # (``host.gc``): none while the burst runs, so the phases named here
    # are all of them
    gc.disable()
    try:
        futs = [sched.submit(p, max_new_tokens=8) for p in _prompts(10)]
        outs = [f.result(timeout=300) for f in futs]
        # the worker closes the last turn's spans AFTER it completes the
        # last future: join it before the cells are read
        sched.stop()
    finally:
        gc.enable()
    snap = {c: v - h0[c] for c, v in _sched_snap().items()}
    cnt = {n: obs.counter("serving.decode." + n).value - c0[n]
           for n in counters}
    return snap, cnt, outs


# -- scheduler phases --------------------------------------------------------

def test_scheduler_phase_counts_follow_the_loop(generate_run):
    s, cnt, outs = generate_run
    assert all(len(o) == 8 for o in outs)
    n = s["iteration"].count
    assert n > 0 and s["iteration.host"].count == n
    # one admit and one sweep a turn; a chunk's three phases and its two
    # children come together; likewise the decode step's
    assert s["admit"].count == s["sweep"].count == n
    assert (s["chunk.build"].count == s["prefill"].count
            == s["prefill.dispatch"].count == s["prefill.wait"].count
            == s["chunk.commit"].count == cnt["prefills"] > 0)
    assert (s["step.build"].count == s["step"].count
            == s["step.dispatch"].count == s["step.wait"].count
            == s["step.commit"].count == cnt["steps"] > 0)
    assert s["prefill"].count <= n and s["step"].count <= n
    # the histogram counts ARE the counters: no second count to drift
    assert cnt["tokens"] == 80 and cnt["requests"] == 10


def test_iteration_mean_is_the_sum_of_its_phase_means(generate_run):
    """iteration = admit + sweep + share x (chunk.build + prefill +
    chunk.commit) + step.build + step + step.commit + what lies between
    them (``iteration.unspanned``): the identity the worker's frame keeps,
    EXACTLY, on sums and on means with every phase weighed by how often it
    ran.  Counts and an identity of one clock's readings: no closeness of
    two, which a loaded machine moves."""
    s, cnt, _ = generate_run
    parts = ("admit", "sweep", "chunk.build", "prefill", "chunk.commit",
             "step.build", "step", "step.commit")
    n = s["iteration"].count
    # how often each phase ran: once a turn, once a chunk, once a step
    assert s["admit"].count == s["sweep"].count == n
    assert s["iteration.unspanned"].count == n
    assert (s["chunk.build"].count == s["prefill"].count
            == s["chunk.commit"].count == cnt["prefills"])
    assert (s["step.build"].count == s["step"].count
            == s["step.commit"].count == cnt["steps"])
    total = sum(s[p].sum for p in parts)
    assert total <= s["iteration"].sum
    assert total + s["iteration.unspanned"].sum == pytest.approx(
        s["iteration"].sum, rel=1e-9)
    by_means = (s["admit"].mean + s["sweep"].mean
                + s["iteration.unspanned"].mean
                + s["prefill"].count / n * (s["chunk.build"].mean
                                            + s["prefill"].mean
                                            + s["chunk.commit"].mean)
                + s["step"].count / n * (s["step.build"].mean + s["step"].mean
                                         + s["step.commit"].mean))
    assert by_means == pytest.approx(s["iteration"].mean, rel=1e-9)


def test_children_stay_inside_their_parents(generate_run):
    s, _, _ = generate_run
    assert s["step"].sum >= s["step.dispatch"].sum + s["step.wait"].sum
    assert s["prefill"].sum >= (s["prefill.dispatch"].sum
                                + s["prefill.wait"].sum)
    assert s["iteration"].sum >= s["step"].sum + s["prefill"].sum


def test_iteration_host_is_the_iteration_less_its_waits(generate_run):
    s, _, _ = generate_run
    waits = s["step.wait"].sum + s["prefill.wait"].sum
    assert s["iteration.host"].sum == pytest.approx(
        s["iteration"].sum - waits, rel=1e-9)
    assert 0 < s["iteration.host"].sum < s["iteration"].sum


def test_the_idle_wait_is_no_iteration_and_no_admit(decode_model):
    sched = serving.DecodeScheduler(decode_model, _cfg())
    try:
        h0, t0 = _sched_snap(), time.perf_counter()
        time.sleep(0.3)
        d = {c: v - h0[c] for c, v in _sched_snap().items()}
        elapsed = time.perf_counter() - t0
    finally:
        sched.stop()
    assert d["idle"].count >= 3
    # whole 50 ms waits: the one under way at either edge is cut off
    assert elapsed - 0.12 <= d["idle"].sum <= elapsed + 0.06
    assert d["iteration"].count == d["admit"].count == 0
    assert d["iteration.host"].count == d["step"].count == 0


def test_the_duplicate_decode_cells_are_gone(generate_run):
    tel = obs.get_telemetry()
    assert not [n for n in tel.timers() if n.startswith("serving.decode.")]
    for gone in ("serving.decode.decode_step", "serving.decode.prefill_step"):
        assert gone not in tel.histograms()
    s, cnt, _ = generate_run
    # what decode_step_ms reads: one observation per decode step, no more
    assert s["step"].count == cnt["steps"]


# -- executor and feed phases ------------------------------------------------

EXE_CELLS = ("run", "first_run", "compile", "dispatch", "prepare_feed",
             "bind", "writeback")


def _exe_snap():
    return {c: obs.histogram("executor." + c).snapshot() for c in EXE_CELLS}


@pytest.mark.parametrize("call, cells", [
    # start-up program: builds its entry
    ("startup", {"first_run": 1, "compile": 1, "run": 0, "dispatch": 0}),
    # a shape's first step builds its entry: never among the replays
    ("first_step", {"first_run": 1, "compile": 1, "run": 0, "dispatch": 0}),
    # the second step replays it on the slow path and binds
    ("second_step", {"first_run": 0, "compile": 0, "run": 1, "dispatch": 1}),
    # from the third on: the bound fast path
    ("bound_step", {"first_run": 0, "compile": 0, "run": 1, "dispatch": 1}),
])
def test_a_call_that_builds_its_entry_has_cells_of_its_own(call, cells):
    main, startup, loss, feed = _train_program()
    exe = fluid.Executor(fluid.CPUPlace())
    order = ["startup", "first_step", "second_step", "bound_step"]
    with fluid.scope_guard(fluid.Scope()):
        for step in order:
            before = _exe_snap()
            if step == "startup":
                exe.run(startup)
            else:
                exe.run(main, feed=feed, fetch_list=[loss])
            if step == call:
                break
        d = {c: v - before[c] for c, v in _exe_snap().items()}
    assert {c: d[c].count for c in cells} == cells
    # every call: one prepare_feed, one bind, one write-back, inside the whole
    assert d["prepare_feed"].count == d["bind"].count == 1
    assert d["writeback"].count == 1
    whole = d["first_run"] if cells["first_run"] else d["run"]
    inner = sum(d[c].sum for c in ("prepare_feed", "bind", "compile",
                                   "dispatch", "writeback"))
    assert inner <= whole.sum
    if call == "bound_step":
        assert len(exe._bound) == 1


def test_executor_run_carries_no_timing_ladder():
    src = inspect.getsource(executor_mod.Executor.run) + inspect.getsource(
        executor_mod.Executor._run_bound)
    assert "_xla_stats" not in src and "span_active" not in src
    assert src.count('span("executor.dispatch")') == 1      # _run_bound's
    assert src.count('"executor.compile" if compiled_fresh') == 1


def test_profiler_session_blocks_inside_the_dispatch_span():
    """``profiler.py``'s is_profiling branch keeps its block_until_ready
    semantics and times through the primitive: its report row and the
    dispatch cell are one measurement."""
    main, startup, loss, feed = _train_program()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        d0 = obs.histogram("executor.dispatch").snapshot()
        fluid.profiler.start_profiler("All")
        try:
            exe.run(main, feed=feed, fetch_list=[loss])
        finally:
            fluid.profiler.stop_profiler(profile_path="/dev/null")
        d = obs.histogram("executor.dispatch").snapshot() - d0
    rows = {n: t for n, t in obs.get_telemetry().timers().items()
            if n.startswith(fluid.profiler.TIMING_PREFIX + "executor.run[")
            and t.count}
    assert d.count == 1 and len(rows) == 1
    assert next(iter(rows.values())).total == d.sum


def test_prefetch_phases_go_through_the_primitive():
    main, startup, loss, feed = _train_program()
    exe = fluid.Executor(fluid.CPUPlace())
    feeder = fluid.DataFeeder(
        feed_list=[main.global_block().var(n) for n in ("x", "y")],
        place=fluid.CPUPlace(), program=main)

    def reader():
        for _ in range(5):
            yield list(zip(feed["x"], feed["y"]))

    cells = ("prefetch.wait", "prefetch.convert_transfer",
             "prefetch.device_put")
    h0 = {c: obs.histogram(c).snapshot() for c in cells}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        it = device_prefetch.decorate_device_feed(reader, feeder, exe, main)()
        n = sum(1 for f in it
                if exe.run(main, feed=f, fetch_list=[loss]) is not None)
    d = {c: obs.histogram(c).snapshot() - h0[c] for c in cells}
    assert n == 5
    assert d["prefetch.wait"].count == 6            # five items and the stop
    assert d["prefetch.convert_transfer"].count == 5
    assert d["prefetch.device_put"].count == 10     # two feeds a batch
    gauges = obs.get_telemetry().gauges()
    assert "prefetch.buffer_occupancy" not in gauges
    assert "prefetch.buffer_capacity" not in gauges


# -- on the profiler's clock -------------------------------------------------

def _host_lines(trace_dir):
    pb = glob.glob(str(trace_dir) + "/**/*.xplane.pb", recursive=True)
    assert len(pb) == 1
    data = jax.profiler.ProfileData.from_file(pb[0])
    plane = next(p for p in data.planes if p.name == "/host:CPU")
    lines = []
    for line in plane.lines:
        evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
               for e in line.events]
        lines.append(evs)
    return lines


def test_spans_lie_in_a_jax_profiler_trace_on_their_threads_line(
        decode_model, tmp_path):
    main, startup, loss, feed = _train_program()
    exe = fluid.Executor(fluid.CPUPlace())
    sched = serving.DecodeScheduler(decode_model, _cfg())
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            exe.run(main, feed=feed, fetch_list=[loss])
            with obs.span("outside.before"):
                pass                            # no session: not in the trace
            jax.profiler.start_trace(str(tmp_path))
            try:
                with jax.profiler.TraceAnnotation("test.session"):
                    futs = [sched.submit(p, max_new_tokens=4)
                            for p in _prompts(3, seed=2)]
                    for _ in range(3):
                        exe.run(main, feed=feed, fetch_list=[loss])
                    for f in futs:
                        f.result(timeout=300)
                    # the worker closes the last turn's spans after it
                    # completes the last future; an idle wait that ends
                    # from here on began after that turn
                    idle = obs.histogram("serving.decode.idle")
                    n_idle = idle.count
                    while idle.count == n_idle:
                        time.sleep(0.01)
            finally:
                jax.profiler.stop_trace()
    finally:
        sched.stop()
    lines = _host_lines(tmp_path)

    def line_with(name):
        # the profiler names a line after the OS thread ("python" for
        # both): tell the lines apart by the events they hold
        found = [evs for evs in lines if any(n == name for n, _, _ in evs)]
        assert len(found) == 1, (name, len(found))
        return found[0]

    worker = line_with(PREFIX + "serving.decode.step.wait")
    caller = line_with(PREFIX + "executor.dispatch")
    assert worker is not caller
    assert not any(n.startswith(PREFIX + "executor.") for n, _, _ in worker)
    assert not any(n.startswith(PREFIX + "serving.") for n, _, _ in caller)
    assert not any(n == PREFIX + "outside.before"
                   for evs in lines for n, _, _ in evs)
    # inside the session's extent, on the same clock
    (s0, s1), = [(a, b) for n, a, b in caller if n == "test.session"]
    ours = [(n, a, b) for n, a, b in worker + caller if n.startswith(PREFIX)]
    assert ours and all(s0 <= a and b <= s1 for _, a, b in ours)

    def inside(line, child, parent):
        kids = [(a, b) for n, a, b in line if n == PREFIX + child]
        parents = [(a, b) for n, a, b in line if n == PREFIX + parent]
        assert kids and parents
        assert all(any(pa <= a and b <= pb for pa, pb in parents)
                   for a, b in kids), (child, parent)

    inside(caller, "executor.dispatch", "executor.run")
    inside(caller, "executor.prepare_feed", "executor.run")
    inside(caller, "executor.writeback", "executor.run")
    inside(worker, "serving.decode.step.wait", "serving.decode.step")
    inside(worker, "serving.decode.step.dispatch", "serving.decode.step")
    inside(worker, "serving.decode.step", "serving.decode.iteration")
    inside(worker, "serving.decode.admit", "serving.decode.iteration")
    inside(worker, "serving.decode.prefill.wait", "serving.decode.prefill")
    inside(worker, "serving.decode.prefill", "serving.decode.iteration")
    inside(worker, "serving.decode.chunk.commit", "serving.decode.iteration")


# -- and it changes nothing --------------------------------------------------

@pytest.mark.parametrize("path", ["generate", "train"])
def test_outputs_bitwise_equal_with_telemetry_off_and_on(path, decode_model):
    tel = obs.get_telemetry()

    def run():
        if path == "generate":
            sched = serving.DecodeScheduler(decode_model, _cfg())
            try:
                futs = [sched.submit(p, max_new_tokens=6, temperature=0.8,
                                     seed=i)
                        for i, p in enumerate(_prompts(6, seed=4))]
                return [np.asarray(f.result(timeout=300)) for f in futs]
            finally:
                sched.stop()
        main, startup, loss, feed = _train_program()
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            losses = [np.asarray(exe.run(main, feed=feed,
                                         fetch_list=[loss])[0])
                      for _ in range(4)]
            params = [np.asarray(scope.vars[n])
                      for n in sorted(main.persistable_names())
                      if n in scope.vars and n != "__rng_key__"]
        fluid.unique_name.switch()
        return losses + params

    try:
        tel.configure(enabled=False)
        off = run()
        tel.configure(enabled=True)
        ring = obs.RingBufferSink(record_spans=True)
        obs.add_sink(ring)
        try:
            on = run()
        finally:
            obs.remove_sink(ring)
    finally:
        tel.configure()
    assert len(off) == len(on) > 0
    for a, b in zip(off, on):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    assert ring.spans       # the second run really was recorded
