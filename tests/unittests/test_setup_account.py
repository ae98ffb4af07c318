"""Set-up accounts for its own time (PR 54): ``obs.watch_compiles()`` turns
jax's compile requests into spans and counters of the registry, each put down
to the set-up or loop span open on the compiling thread; ``DecodeScheduler``'s
construction is a span with its parts; the package's import is a cell and a
gauge.

Counts, orders and extents that nest: no ratio of two clocks' readings.
"""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax._src import monitoring as jax_monitoring  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu import serving  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402
from paddle_tpu.observability import startup  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
STAGES = ("xla.compile.trace", "xla.compile.lower", "xla.compile.backend")
WARMUP = "serving.decode.warmup"


@pytest.fixture(autouse=True)
def watching():
    obs.watch_compiles()
    yield
    obs.watch_compiles()        # a test that took it out puts it back


def _cell(name, within):
    return obs.histogram(name, {"within": within}).snapshot()


def _count(name, within):
    return obs.counter("xla.compile." + name, {"within": within}).value


def _stages(within):
    return {name: _cell(name, within) for name in STAGES}


_fresh = [0]


def _new_program(inner=False):
    """A jitted function jax has not seen: tracing, lowering and compiling it
    are each reported once.  With ``inner`` it calls two jitted functions of
    its own, whose traces lie inside its own."""
    _fresh[0] += 1
    k = float(_fresh[0])
    if inner:
        def inner_a(x):
            return x * k + 1.0

        def inner_b(x):
            return jnp.tanh(x) - k

        a, b = jax.jit(inner_a), jax.jit(inner_b)

        def outer_fn(x):
            return jnp.sum(a(x) @ b(x))
    else:
        def outer_fn(x):
            return jnp.sum(x * k)
    outer_fn.__name__ = "fresh_%d" % _fresh[0]
    return jax.jit(outer_fn), outer_fn.__name__


X = np.ones((4, 4), np.float32)


@pytest.fixture
def ring():
    sink = obs.RingBufferSink(capacity=1 << 14, record_spans=True)
    obs.add_sink(sink)
    yield sink
    obs.remove_sink(sink)


def _compile_spans(ring, fun):
    return [s for s in ring.spans if s["name"] in STAGES
            and s["tags"]["fun"] in (fun, "jit(%s)" % fun)]


# -- the watcher --------------------------------------------------------------

def test_watching_twice_registers_once():
    before = (len(jax_monitoring.get_event_time_span_listeners()),
              len(jax_monitoring.get_event_listeners()),
              len(jax_monitoring.get_event_duration_listeners()))
    assert obs.watch_compiles() is obs.watch_compiles()
    assert before == (len(jax_monitoring.get_event_time_span_listeners()),
                      len(jax_monitoring.get_event_listeners()),
                      len(jax_monitoring.get_event_duration_listeners()))
    obs.unwatch_compiles()
    obs.unwatch_compiles()      # idempotent too
    assert (len(jax_monitoring.get_event_time_span_listeners()),
            len(jax_monitoring.get_event_listeners()),
            len(jax_monitoring.get_event_duration_listeners())) == tuple(
                n - 1 for n in before)


def test_every_sites_cells_are_there_before_anything_compiled_there():
    tel = obs.get_telemetry()
    cells, counters = tel.histograms(), tel.counters()
    for site in startup.SITES:
        for name in STAGES + ("xla.compile.cache_retrieval",):
            assert obs.labeled_name(name, {"within": site}) in cells
        for name in ("requests", "cache_hits", "cache_misses"):
            assert obs.labeled_name("xla.compile." + name,
                                    {"within": site}) in counters
    assert "xla.compile.seconds_saved" in counters


def test_a_compile_is_put_down_to_the_setup_span_that_caused_it(ring):
    warm, warm_name = _new_program()
    bare, bare_name = _new_program()
    w0, o0 = _stages(WARMUP), _stages("other")
    r0 = (_count("requests", WARMUP), _count("requests", "other"))
    with obs.setup_span(WARMUP):
        warm(X)
    bare(X)
    for name in STAGES:
        # one lowering and one compile a program; its trace holds those of
        # the jnp functions it calls (each a span of its own, the first time)
        want = (lambda n: n >= 1) if name.endswith("trace") else (
            lambda n: n == 1)
        assert want((_cell(name, WARMUP) - w0[name]).count), name
        assert want((_cell(name, "other") - o0[name]).count), name
    assert _count("requests", WARMUP) == r0[0] + 1
    assert _count("requests", "other") == r0[1] + 1
    # the sinks got the same spans, with jax's name of the function, in
    # the order jax made them
    for fun, within in ((warm_name, WARMUP), (bare_name, "other")):
        spans = _compile_spans(ring, fun)
        assert [s["name"] for s in spans] == list(STAGES)
        assert {s["tags"]["within"] for s in spans} == {within}
        assert all(s["dur"] >= 0 for s in spans)
        assert spans[0]["ts"] <= spans[1]["ts"] <= spans[2]["ts"]


def test_the_innermost_site_wins_and_the_outer_one_comes_back():
    inside, _ = _new_program()
    after, _ = _new_program()
    w0 = _count("requests", WARMUP)
    b0 = _count("requests", "serving.decode.build")
    o0 = _count("requests", "other")
    with obs.setup_span("serving.decode.build"):
        with obs.setup_span(WARMUP):
            inside(X)
        after(X)
    assert _count("requests", WARMUP) == w0 + 1
    assert _count("requests", "serving.decode.build") == b0 + 1
    assert _count("requests", "other") == o0


def test_a_site_is_its_threads_own():
    prog, _ = _new_program()
    o0, w0 = _count("requests", "other"), _count("requests", WARMUP)
    with obs.setup_span(WARMUP):
        t = threading.Thread(target=prog, args=(X,))
        t.start()
        t.join()
    assert _count("requests", "other") == o0 + 1
    assert _count("requests", WARMUP) == w0


def test_a_nested_trace_is_counted_once(ring):
    prog, name = _new_program(inner=True)
    t0 = _cell("xla.compile.trace", WARMUP)
    with obs.setup_span(WARMUP):
        prog(X)
    traced = _cell("xla.compile.trace", WARMUP) - t0
    outer = [s for s in _compile_spans(ring, name)
             if s["name"] == "xla.compile.trace"]
    assert len(outer) == 1
    # the functions it calls were traced inside its own extent ...
    inner = [s for s in ring.spans if s["name"] == "xla.compile.trace"
             and s["tags"]["fun"] in ("inner_a", "inner_b")]
    assert len(inner) == 2
    for s in inner:
        assert outer[0]["ts"] <= s["ts"]
        assert s["ts"] + s["dur"] <= outer[0]["ts"] + outer[0]["dur"] + 1e-6
    # ... and the cell holds every one of them by its SELF time: together
    # the outer trace's extent, not that and the inner ones again
    assert traced.count >= 3
    assert traced.sum <= outer[0]["dur"] + 1e-6
    assert traced.sum == pytest.approx(outer[0]["dur"], abs=1e-6)


def test_nothing_is_heard_once_unwatched():
    prog, _ = _new_program()
    obs.unwatch_compiles()
    o0 = _count("requests", "other")
    prog(X)
    assert _count("requests", "other") == o0


def test_a_compile_on_a_thread_with_a_frame_is_in_the_frames_phases():
    prog, _ = _new_program()
    tel = obs.Telemetry(enabled=True)
    frame = obs.open_frame()
    try:
        with tel.span("turn"):
            with tel.span("turn.dispatch"):
                prog(X)
    finally:
        obs.close_frame()
    for name in STAGES:
        seconds, spans, depth = frame.phases[
            obs.labeled_name(name, {"within": "other"})]
        assert depth == 2 and seconds >= 0
        # (a trace holds those of the jnp functions the program calls)
        assert spans >= 1 if name.endswith("trace") else spans == 1
    # a child of ``turn.dispatch``, not of the turn: the turn's own
    # children are what they were
    assert frame.children_s == frame.phases["turn.dispatch"][0]


def test_past_span_observes_adds_to_the_frame_and_feeds_the_sinks():
    tel = obs.Telemetry(enabled=True)
    sink = obs.RingBufferSink(record_spans=True)
    tel.add_sink(sink)
    frame = obs.open_frame()
    try:
        with tel.span("turn"):
            tel.past_span("theirs", 100.0, 2.0, labels={"by": "x"},
                          tags={"fun": "f"}, seconds=0.5)
    finally:
        obs.close_frame()
    cell = tel.histogram("theirs", {"by": "x"}).snapshot()
    assert (cell.count, cell.sum) == (1, 0.5)
    assert frame.phases['theirs{by="x"}'] == [0.5, 1, 1]
    assert frame.children_s == 0.5
    theirs = [s for s in sink.spans if s["name"] == "theirs"]
    assert [(s["ts"], s["dur"], s["tags"]["fun"]) for s in theirs] == [
        (100.0, 2.0, "f")]


# -- the executor -------------------------------------------------------------

def test_an_entrys_first_run_owns_its_compiles_and_no_more_than_its_extent():
    main, startup_prog = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup_prog):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h = fluid.layers.fc(input=x, size=16, act="relu")
        loss = fluid.layers.mean(h)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    site = "executor.first_run"
    s0, r0 = _stages(site), _count("requests", site)
    first0 = obs.histogram(site).snapshot()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup_prog)
        feed = {"x": np.ones((4, 8), np.float32)}
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss])
        requests = _count("requests", site) - r0
        # the start-up program and the step: one request each at least,
        # and none from the replays
        assert requests >= 2
        exe.run(main, feed=feed, fetch_list=[loss])
        assert _count("requests", site) - r0 == requests
    first = obs.histogram(site).snapshot() - first0
    assert first.count == 2
    spent = sum((_cell(n, site) - s0[n]).sum for n in STAGES)
    assert 0 < spent <= first.sum


# -- the decode scheduler -----------------------------------------------------

@pytest.fixture(scope="module")
def decode_model():
    params, meta = T.lm_params(seed=7, vocab_size=50, n_layer=2, n_head=2,
                               d_model=48, d_inner=64, max_length=128)
    return T.build_decode_model(params, meta)


def _cfg(**kw):
    base = dict(num_slots=3, page_size=8, max_seq_len=64, max_new_tokens=6,
                prefill_chunk_tokens=16)
    base.update(kw)
    return serving.DecodeConfig(**base)


BUILD_CELLS = ("serving.decode.build", "serving.cache.allocate",
               "serving.model_load", WARMUP)
UNSPANNED = "serving.decode.build.unspanned"


def test_a_schedulers_construction_is_a_span_with_its_parts(decode_model,
                                                            ring):
    c0 = {c: obs.histogram(c).snapshot() for c in BUILD_CELLS + (UNSPANNED,)}
    warm0 = _stages(WARMUP)
    r0 = {s: _count("requests", s) for s in startup.SITES}
    sched = serving.DecodeScheduler(decode_model, _cfg())
    try:
        d = {c: obs.histogram(c).snapshot() - c0[c] for c in c0}
        assert [d[c].count for c in BUILD_CELLS] == [1, 1, 1, 1]
        build = d["serving.decode.build"].sum
        parts = sum(d[c].sum for c in BUILD_CELLS[1:])
        assert 0 < parts <= build
        # what lies under none of the three is kept by the scheduler itself
        # (one observation a construction): the whole less its parts, the
        # call of ``warmup()`` around its span counted with the span
        assert d[UNSPANNED].count == 1
        assert 0 <= d[UNSPANNED].sum <= build - parts + 1e-9
        assert d[UNSPANNED].sum == pytest.approx(build - parts, abs=1e-3)
        # the parts close inside the whole, in the order they are built
        closed = [s["name"] for s in ring.spans if s["name"] in BUILD_CELLS]
        assert closed == ["serving.cache.allocate", "serving.model_load",
                          WARMUP, "serving.decode.build"]
        # warm-up compiled the step programs, and its compile spans are no
        # more than its own extent (each second counted once)
        assert _count("requests", WARMUP) - r0[WARMUP] >= 2
        spent = sum((_cell(n, WARMUP) - warm0[n]).sum for n in STAGES)
        assert 0 < spent <= d[WARMUP].sum
        # the warmed menu holds: serving compiles nothing in the loop
        loop0 = _count("requests", "serving.decode.iteration")
        rng = np.random.RandomState(0)
        futs = [sched.submit(rng.randint(1, 50, size=n).astype(np.int32),
                             max_new_tokens=6) for n in (5, 30, 17, 40)]
        assert all(len(f.result(timeout=300)) == 6 for f in futs)
        assert _count("requests", "serving.decode.iteration") == loop0
    finally:
        sched.stop()


def test_a_compile_in_the_loop_is_the_loops(decode_model, monkeypatch):
    """A shape that escaped the warmed menu compiles on the worker's thread:
    it is ``within="serving.decode.iteration"``, and in the worker's frame."""
    escaped, _ = _new_program()
    sched = serving.DecodeScheduler(decode_model, _cfg(), autostart=False)
    seen = {}
    note = sched._note_throughput

    def note_and_compile():
        if not seen:
            escaped(X)          # inside the turn, under none of its phases
            seen.update(sched._frame.phases)
        note()

    monkeypatch.setattr(sched, "_note_throughput", note_and_compile)
    loop0 = _count("requests", "serving.decode.iteration")
    other0 = _count("requests", "other")
    sched.start()
    try:
        out = sched.submit(np.arange(1, 9, dtype=np.int32),
                           max_new_tokens=4).result(timeout=300)
        assert len(out) == 4
    finally:
        sched.stop()
    assert _count("requests", "serving.decode.iteration") == loop0 + 1
    assert _count("requests", "other") == other0
    backend = obs.labeled_name(
        "xla.compile.backend", {"within": "serving.decode.iteration"})
    assert seen[backend][1:] == [1, 1]      # one span, a child of the turn


# -- a process start, and the persistent cache --------------------------------

_COLD_THEN_WARM = r"""
import json, sys
import paddle_tpu as fluid
from paddle_tpu import observability as obs
import jax, jax.numpy as jnp

assert fluid.enable_compilation_cache()

@jax.jit
def program(x, w):
    return jnp.sum(jnp.tanh(x @ w))

def counts():
    tel = obs.get_telemetry()
    site = {"within": "serving.decode.warmup"}
    return {
        "requests": tel.counter("xla.compile.requests", site).value,
        "hits": tel.counter("xla.compile.cache_hits", site).value,
        "misses": tel.counter("xla.compile.cache_misses", site).value,
        "retrievals": tel.histogram("xla.compile.cache_retrieval", site).count,
        "retrieval_s": tel.histogram(
            "xla.compile.cache_retrieval", site).snapshot().sum,
        "saved_s": tel.counter("xla.compile.seconds_saved").value,
    }

x = jnp.ones((8, 8))
out = {"import_done_s": obs.gauge("process.import_done_s").value,
       "imports": obs.histogram("process.import").count,
       "import_s": obs.histogram("process.import").snapshot().sum}
passes = []
for _ in range(2):
    with obs.setup_span("serving.decode.warmup"):
        program(x, x).block_until_ready()
    passes.append(counts())
    jax.clear_caches()      # what a new process has: nothing in memory
out["passes"] = passes
print("RESULT " + json.dumps(out))
"""


def test_a_cold_pass_counts_misses_and_a_warm_one_hits(tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_ENABLE_COMPILATION_CACHE="1", JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT)
    done = subprocess.run([sys.executable, "-c", _COLD_THEN_WARM], env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-4000:]
    line = [ln for ln in done.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    cold, warm = out["passes"]
    # the first pass compiled and wrote; the second asked again and was
    # answered by the directory
    assert cold["requests"] == 1 and warm["requests"] == 2
    assert (cold["misses"], cold["hits"], cold["retrievals"]) == (1, 0, 0)
    assert (warm["misses"], warm["hits"], warm["retrievals"]) == (1, 1, 1)
    assert warm["retrieval_s"] > 0 and warm["saved_s"] >= 0
    # and the process knows how old it was when the package was imported
    assert out["imports"] == 1
    assert 0 < out["import_s"] <= out["import_done_s"] + 0.01
