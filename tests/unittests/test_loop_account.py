"""The serving loop's account of its own time (PR 38): the per-thread frame
that every span feeds, ``iteration.host`` / ``iteration.unspanned`` read from
it, commit-to-commit intervals and the stalls judged from them (with the
phase each is put down to), the collector as a span, and the prefill chunk's
own time apart from the decode step in flight ahead of it.

Wall-clock tests inject sleeps at least four times the threshold they must
cross, and raise the floor so that a loaded machine's slow turn stays under
it; the rule itself is tested on a clock the test owns.
"""
import gc
import threading
import time
import types

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu import serving  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402
from paddle_tpu.serving import decode_scheduler as ds  # noqa: E402
from paddle_tpu.testing import faults  # noqa: E402

from test_decode_serving import _arrive_behind_a_commit  # noqa: E402

D = "serving.decode."


@pytest.fixture(scope="module")
def decode_model():
    params, meta = T.lm_params(seed=7, vocab_size=50, n_layer=2, n_head=2,
                               d_model=32, d_inner=64, max_length=256)
    return T.build_decode_model(params, meta)


def _cfg(**kw):
    base = dict(num_slots=4, page_size=8, max_seq_len=160, max_new_tokens=8,
                prefill_chunk_tokens=16)
    base.update(kw)
    return serving.DecodeConfig(**base)


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 50, size=n).astype(np.int32)


def _cell(name):
    return obs.histogram(name).snapshot()


# -- the frame ----------------------------------------------------------------

def test_a_frame_holds_what_closed_on_its_thread():
    tel = obs.Telemetry(enabled=True)
    frame = obs.open_frame()
    try:
        with tel.span("turn"):
            with tel.span("a"):
                with tel.span("a.wait"):
                    time.sleep(0.002)
            with tel.span("b") as dropped:
                dropped.name = None          # closes into no cell: not held
            with tel.span("a"):
                pass
        assert frame.depth == 0
        assert {n: (e[1], e[2]) for n, e in frame.phases.items()} == {
            "turn": (1, 0), "a": (2, 1), "a.wait": (1, 2)}
        assert frame.children_s == frame.phases["a"][0]
        assert frame.wait_s == frame.phases["a.wait"][0] >= 0.002
        assert frame.phases["a"][0] == tel.histogram("a").snapshot().sum
        held = frame.cut()
        assert set(held) == {"turn", "a", "a.wait"} and frame.phases == {}
        # the running totals go on through a cut
        assert frame.children_s == held["a"][0]
    finally:
        obs.close_frame()
    with tel.span("a"):
        pass
    assert frame.phases == {}


def test_a_frame_belongs_to_one_thread():
    tel = obs.Telemetry(enabled=True)
    frame = obs.open_frame()
    try:
        def other():
            with tel.span("elsewhere"):
                pass
        t = threading.Thread(target=other)
        t.start()
        t.join()
        with tel.span("here"):
            pass
        assert set(frame.phases) == {"here"}
    finally:
        obs.close_frame()


def test_a_held_span_closes_once_with_the_sum_of_its_two_extents():
    """A phase paid in two parts (the chunk's ``prefill``): the first exit
    reaches no cell, frame or sink and leaves the depth as it found it; the
    second closes the span at its depth with both extents, the work between
    them apart."""
    tel = obs.Telemetry(enabled=True)
    ring = obs.RingBufferSink(capacity=16, record_spans=True)
    tel.add_sink(ring)
    frame = obs.open_frame()
    try:
        with tel.span("turn"):
            part = tel.span("a", k=1)
            with part:
                part.hold()
                with tel.span("a.dispatch"):
                    time.sleep(0.002)
            first = part.duration
            assert frame.depth == 1 and "a" not in frame.phases
            assert tel.histogram("a").snapshot().count == 0
            with tel.span("b"):
                time.sleep(0.01)             # not the held span's time
            with part:
                with tel.span("a.wait"):
                    time.sleep(0.002)
        assert {n: (e[1], e[2]) for n, e in frame.phases.items()} == {
            "turn": (1, 0), "a.dispatch": (1, 2), "b": (1, 1),
            "a.wait": (1, 2), "a": (1, 1)}
        whole = frame.phases["a"][0]
        assert whole == part.duration == tel.histogram("a").snapshot().sum
        assert first >= 0.002 and first + 0.002 <= whole < first + 0.01
        assert frame.children_s == whole + frame.phases["b"][0]
        assert [s["name"] for s in ring.spans] == [
            "a.dispatch", "b", "a.wait", "a", "turn"]
    finally:
        obs.close_frame()


class _Tap:
    """Stands in for a histogram cell: keeps each observation, and passes it
    on."""

    def __init__(self, cell):
        self.cell, self.seen = cell, []
        self.name = cell.name

    def observe(self, v):
        self.seen.append(v)
        self.cell.observe(v)

    def snapshot(self):
        return self.cell.snapshot()


def test_the_iteration_adds_up_exactly_and_host_is_what_the_parent_gave(
        decode_model, monkeypatch):
    """Direct children + ``unspanned`` = the iteration, and ``iteration.host``
    equals, turn by turn, what the parent's hand-kept ``_turn_wait_s`` gave:
    the turn less the ``*.wait`` spans that closed inside it."""
    host = _Tap(ds._iteration_host)
    between = _Tap(ds._iteration_unspanned)
    monkeypatch.setattr(ds, "_iteration_host", host)
    monkeypatch.setattr(ds, "_iteration_unspanned", between)
    ring = obs.RingBufferSink(capacity=1 << 16, record_spans=True)
    obs.add_sink(ring)
    gc.disable()            # a collection between two spans is a child too
    try:
        sched = serving.DecodeScheduler(decode_model, _cfg())
        futs = [sched.submit(_prompt(n, n), max_new_tokens=8)
                for n in (5, 40, 23, 70, 9, 33)]
        for f in futs:
            f.result(timeout=300)
        sched.stop()
    finally:
        gc.enable()
        obs.remove_sink(ring)
    spans = [s for s in ring.spans if s["name"].startswith(D)]
    # spans reach the sink as they close: what closed since the iteration
    # before belongs to this one
    turns, inside = [], []
    for s in spans:
        if s["name"] == D + "iteration":
            turns.append((s["dur"], inside))
            inside = []
        elif s["name"] != D + "idle":
            inside.append(s)
    assert len(turns) == len(host.seen) == len(between.seen) > 10
    children = {D + n for n in ("admit", "sweep", "chunk.build", "prefill",
                                "chunk.commit", "step.build", "step",
                                "step.commit")}
    for (dur, closed), h, u in zip(turns, host.seen, between.seen):
        waits = sum(s["dur"] for s in closed if s["name"].endswith(".wait"))
        assert h == pytest.approx(dur - waits, rel=1e-9, abs=1e-12)
        direct = sum(s["dur"] for s in closed if s["name"] in children)
        assert direct + u == pytest.approx(dur, rel=1e-9, abs=1e-12)
        assert u >= 0


# -- the rule, on a clock the test owns ------------------------------------------

class _Clock:
    def __init__(self):
        self.now = 100.0
        self.cpu = 1.0
        self.cpu_reads = 0

    def perf_counter(self):
        return self.now

    def thread_time(self):
        self.cpu_reads += 1
        return self.cpu

    def time(self):
        return 1.7e9 + self.now


@pytest.fixture
def judged(decode_model, monkeypatch):
    """A scheduler whose worker never runs, and the clock ``_note_commit``
    reads: ``commit(seconds, chunk, phases)`` is one interval."""
    clock = _Clock()
    monkeypatch.setattr(ds, "time", clock)
    sched = serving.DecodeScheduler(decode_model, _cfg(warmup=False),
                                    autostart=False)
    sched._gc = types.SimpleNamespace(seconds=0.0)
    frame = obs.Frame()
    stall0 = _cell(D + "stall")

    def commit(seconds, chunk=False, phases=None, cpu=None, gc_s=0.0,
               retried=False):
        clock.now += seconds
        clock.cpu += seconds if cpu is None else cpu
        sched._gc.seconds += gc_s
        sched._chunk_rode, sched._retried = chunk, retried
        for name, (s, depth) in (phases or {}).items():
            frame.add(name, s, depth)
        sched._note_commit(frame)

    commit(0.0)                                 # the anchor
    yield sched, commit, lambda: _cell(D + "stall") - stall0
    sched.stop()


def test_nothing_is_judged_before_32_samples_of_the_kind(judged):
    sched, commit, stalls = judged
    for _ in range(10):
        commit(0.020)
    commit(1.0)                                 # sample 11: not judged
    for _ in range(ds.STALL_MIN_SAMPLES - 11):
        commit(0.020)
    assert stalls().count == 0 and sched.stats()["stalls"]["count"] == 0
    assert sched._samples == [ds.STALL_MIN_SAMPLES, 0]
    commit(1.0, phases={D + "step": (0.99, 1)})
    assert stalls().count == 1
    # a chunk's interval is another kind, with its own 32 to wait for
    commit(1.0, chunk=True)
    assert stalls().count == 1 and sched._samples[1] == 1


def test_quiet_iterations_with_a_chunk_every_fifth_yield_no_stall(judged):
    """A 512-token chunk beside a 22 ms step is three steps long and over the
    floor: its own kind's baseline keeps it out."""
    sched, commit, stalls = judged
    for i in range(200):
        if i % 5 == 4:
            commit(0.072, chunk=True)
        else:
            commit(0.022)
    assert stalls().count == 0
    assert sched._baseline[0] == pytest.approx(0.022)
    assert sched._baseline[1] == pytest.approx(0.072)
    # ... and the same 72 ms without a chunk is one
    commit(0.072, phases={D + "step": (0.07, 1), D + "step.wait": (0.069, 2)})
    assert stalls().count == 1
    assert stalls().sum == pytest.approx(0.072 - 0.022)
    last = sched.stats()["stalls"]["last"]
    assert last["where"] == D + "step.wait" and last["chunk"] is False


def test_a_stall_and_a_retry_stay_out_of_the_baseline(judged):
    sched, commit, stalls = judged
    for _ in range(40):
        commit(0.010)
    base = sched._baseline[0]
    commit(0.5)                                  # under 3 x nothing: a stall
    commit(0.030, retried=True)                  # no stall, but no sample
    assert stalls().count == 1 and sched._baseline[0] == base
    assert sched._samples[0] == 40
    # the floor: 40 ms is four baselines and no stall
    commit(0.040)
    assert stalls().count == 1 and sched._samples[0] == 41


def test_the_cpu_clock_is_read_once_in_20_ms_and_at_a_stall(judged):
    """``time.thread_time()`` is a system call (the one in the loop's
    account): a 3 ms iteration does not pay it every turn, and a stall's
    ``cpu_s`` covers the interval and at most 20 ms and a quiet interval
    before it (``cpu_over_s``)."""
    sched, commit, stalls = judged
    clock = ds.time
    reads0 = clock.cpu_reads
    for _ in range(70):
        commit(0.003)                            # 0.21 s: a reading in 7
    assert clock.cpu_reads - reads0 == 10
    commit(0.2, cpu=0.001)
    assert clock.cpu_reads - reads0 == 11
    entry = sched.stalls()[-1]
    assert entry["cpu_over_s"] == pytest.approx(0.2)    # read at commit 70
    assert entry["cpu_s"] == pytest.approx(0.001)
    for _ in range(3):
        commit(0.003)
    commit(0.2, cpu=0.001)                       # three quiet turns before
    entry = sched.stalls()[-1]
    assert entry["cpu_over_s"] == pytest.approx(0.209)
    assert entry["cpu_s"] == pytest.approx(0.010)
    assert entry["interval_s"] <= entry["cpu_over_s"] < (
        entry["interval_s"] + ds.STALL_CPU_EVERY_S + 0.003)
    # a standing cell's 22 ms step reads it every turn
    sched._lose_anchor()
    commit(0.0)
    reads0 = clock.cpu_reads
    for _ in range(10):
        commit(0.022)
    assert clock.cpu_reads - reads0 == 10


def test_a_lasting_shift_becomes_the_baseline_after_32_stalls_in_a_row(judged):
    """A batch or a context many times larger is no stall for good: a kind
    that has stalled for as long as it took to trust its baseline takes the
    run's mean as its baseline; a burst shorter than that leaves it alone."""
    sched, commit, stalls = judged
    for _ in range(40):
        commit(0.010)
    for _ in range(ds.STALL_MIN_SAMPLES - 1):
        commit(0.200)
    commit(0.010)                               # the burst ends: a run no more
    assert stalls().count == ds.STALL_MIN_SAMPLES - 1
    assert sched._baseline[0] == pytest.approx(0.010)
    for _ in range(ds.STALL_MIN_SAMPLES):
        commit(0.200)
    assert stalls().count == 2 * ds.STALL_MIN_SAMPLES - 1
    assert sched._baseline[0] == pytest.approx(0.200)
    for _ in range(10):
        commit(0.200)                           # the new regime: quiet
    assert stalls().count == 2 * ds.STALL_MIN_SAMPLES - 1
    commit(0.700)                               # and judged against itself
    assert stalls().count == 2 * ds.STALL_MIN_SAMPLES


@pytest.mark.parametrize("phases, gc_s, where", [
    # the child that slept, not the parent that holds it
    ({"step": (0.41, 1), "step.wait": (0.40, 2), "step.commit": (0.001, 1)},
     0.0, D + "step.wait"),
    # a parent that slept between its children
    ({"step": (0.41, 1), "step.wait": (0.005, 2), "step.dispatch": (0.002, 2)},
     0.0, D + "step"),
    # nothing below the turn holds it
    ({"step": (0.005, 1), "step.commit": (0.001, 1)}, 0.0, "outside"),
    ({}, 0.0, "outside"),
    # collections cover more than half of the interval
    ({"step": (0.41, 1), "step.build": (0.40, 2)}, 0.3, "gc"),
    ({"step": (0.41, 1), "step.build": (0.40, 2)}, 0.1, D + "step.build"),
])
def test_a_stall_is_put_down_to_a_phase(judged, phases, gc_s, where):
    sched, commit, stalls = judged
    quiet = {D + "step": (0.005, 1), D + "step.wait": (0.004, 2),
             D + "step.dispatch": (0.0005, 2), D + "step.commit": (0.0005, 1)}
    for name, (s, _) in quiet.items():           # what the cells know
        for _ in range(50):
            obs.histogram(name).observe(s)
    for _ in range(40):
        commit(0.006, phases=quiet)
    seconds0 = obs.counter(D + "stall_seconds", {"where": where}).value
    commit(0.42, phases={D + n: v for n, v in phases.items()}, cpu=0.002,
           gc_s=gc_s)
    assert stalls().count == 1
    entry = sched.stalls()[-1]
    assert entry["where"] == where
    assert entry["interval_s"] == pytest.approx(0.42)
    assert entry["excess_s"] == pytest.approx(0.42 - entry["baseline_s"])
    assert entry["cpu_s"] == pytest.approx(0.002) and entry["gc_s"] == gc_s
    assert entry["cpu_over_s"] == pytest.approx(0.42)   # read 40 commits in
    assert entry["frame"] == {D + n: v[0] for n, v in phases.items()}
    assert (obs.counter(D + "stall_seconds", {"where": where}).value
            - seconds0) == pytest.approx(entry["excess_s"])
    assert sched.stats()["stalls"] == {
        "count": 1, "seconds": entry["excess_s"], "last": entry}


def test_the_journal_keeps_the_last_64(judged):
    sched, commit, stalls = judged
    for _ in range(40):
        commit(0.010)
    for _ in range(ds.STALL_RING + 6):
        commit(0.2)
        commit(0.010)                 # no run of stalls: no new regime
    assert len(sched.stalls()) == ds.STALL_RING
    assert sched.stats()["stalls"]["count"] == ds.STALL_RING + 6 == stalls().count


def test_the_first_commit_after_an_idle_wait_observes_nothing(judged):
    sched, commit, stalls = judged
    for _ in range(40):
        commit(0.010)
    n = _cell(D + 'interval{chunk="0"}').count
    sched._lose_anchor()
    commit(5.0)                                  # measured from nothing
    assert _cell(D + 'interval{chunk="0"}').count == n and stalls().count == 0
    commit(0.010)
    assert _cell(D + 'interval{chunk="0"}').count == n + 1


# -- the loop itself --------------------------------------------------------------

def _sleep_once_when(armed, seconds):
    def maybe(*_):
        if armed and armed.pop():
            time.sleep(seconds)
    return maybe


@pytest.mark.parametrize("site, where", [
    ("chaos", D + "step"),              # the choke point: in step, in no child
    ("dispatch", D + "step.dispatch"),
    ("commit", D + "step.commit"),
    ("between", "outside"),
    ("collector", "gc"),
])
def test_one_provoked_stall_is_one_entry_with_its_phase(
        decode_model, monkeypatch, site, where):
    """40 quiet decode steps, then ONE sleep of four floors: exactly one stall,
    put down to where the sleep was; ``health()`` and the record carry it."""
    monkeypatch.setattr(ds, "STALL_FLOOR_S", 0.25)
    nap = 4 * ds.STALL_FLOOR_S
    armed = []
    maybe = _sleep_once_when(armed, nap)
    ring = obs.RingBufferSink()
    obs.add_sink(ring)
    hook = None
    engine = serving.InferenceEngine(
        decode_model=decode_model, decode_config=_cfg(max_new_tokens=120))
    sched = engine.decoder
    try:
        if site == "chaos":
            hook = faults.slow_execute(nap, match=lambda reqs: bool(
                armed and armed.pop()))
            hook.__enter__()
        elif site == "dispatch":
            get = sched._jit.get

            def slow_get(key):
                fn = get(key)
                if key != ("decode",):
                    return fn
                return lambda *a, **k: (maybe(), fn(*a, **k))[1]
            monkeypatch.setattr(sched._jit, "get", slow_get)
        elif site == "commit":
            publish = sched._cache.publish_gauges
            monkeypatch.setattr(sched._cache, "publish_gauges",
                                lambda n: (maybe(), publish(n))[1])
        elif site == "between":
            note = sched._note_throughput
            monkeypatch.setattr(sched, "_note_throughput",
                                lambda: (maybe(), note())[1])
        else:
            # a generation-2 collection ON the worker that takes a nap long:
            # a callback behind the watcher's sleeps inside the collection
            def slow_gc(phase, info):
                if phase == "start" and info["generation"] == 2:
                    time.sleep(nap)

            def collect_once():
                if armed and armed.pop():
                    gc.callbacks.append(slow_gc)
                    try:
                        gc.collect(2)
                    finally:
                        gc.callbacks.remove(slow_gc)
            note = sched._note_throughput
            monkeypatch.setattr(sched, "_note_throughput",
                                lambda: (collect_once(), note())[1])
        fut = engine.generate_async(_prompt(12), max_new_tokens=120)
        while len(fut.token_times) < 45 and not fut.done():
            time.sleep(0.005)
        before = engine.health()["decode"]["stalls"]
        armed.append(True)
        fut.result(timeout=300)
        after = engine.health()["decode"]["stalls"]
    finally:
        if hook is not None:
            hook.__exit__(None, None, None)
        engine.stop()
        obs.remove_sink(ring)
    assert before["count"] == 0 and before["last"] is None
    assert after["count"] == 1, sched.stalls()
    entry = after["last"]
    assert entry["where"] == where, entry
    assert entry["interval_s"] >= nap
    assert after["seconds"] == entry["excess_s"] >= nap - entry["baseline_s"]
    assert entry["active"] == 1 and entry["chunk"] is False
    if site == "collector":
        assert entry["gc_s"] >= nap
    else:
        # the thread slept: wall time far above its CPU time
        assert entry["cpu_s"] < 0.5 * entry["interval_s"]
    records = [r for r in ring.records if r.get("type") == "serving_stall"]
    assert len(records) == 1
    assert {k: records[0][k] for k in entry} == entry
    assert records[0]["source"] == "serving"


# -- the collector as a span ---------------------------------------------------------

def test_watch_gc_is_idempotent_and_unwatch_takes_it_out():
    obs.unwatch_gc()
    n = len(gc.callbacks)
    watch = obs.watch_gc()
    assert obs.watch_gc() is watch and len(gc.callbacks) == n + 1
    try:
        gen2 = obs.histogram("host.gc", {"gen": 2})
        assert gen2.name == 'host.gc{gen="2"}'
        c0, s0 = gen2.snapshot(), watch.seconds
        gc.collect(2)
        d = gen2.snapshot() - c0
        assert d.count == 1 and d.sum > 0
        assert watch.seconds - s0 >= d.sum
    finally:
        obs.unwatch_gc()
    assert len(gc.callbacks) == n
    c1 = gen2.snapshot()
    gc.collect(2)
    assert (gen2.snapshot() - c1).count == 0
    obs.unwatch_gc()                             # and again: nothing to do
    obs.watch_gc()                               # as a started scheduler left it


def test_a_collection_reaches_no_span_sink_and_lands_in_the_frame():
    """The collector's span closes inside whatever it interrupted, which may
    hold a sink's lock: it feeds its cell and the thread's frame only."""
    obs.watch_gc()
    ring = obs.RingBufferSink(record_spans=True)
    obs.add_sink(ring)
    frame = obs.open_frame()
    try:
        gc.collect(0)
    finally:
        obs.close_frame()
        obs.remove_sink(ring)
    assert 'host.gc{gen="0"}' in frame.phases
    assert not [s for s in ring.spans if s["name"].startswith("host.gc")]


def _finishes(body, seconds=30.0):
    """Run ``body`` on a thread of its own: a deadlock fails the test and
    does not hang the run."""
    failed = []

    def run():
        try:
            body()
        except BaseException as e:     # noqa: BLE001 — handed to the test
            failed.append(e)
    th = threading.Thread(target=run, daemon=True)
    th.start()
    th.join(seconds)
    assert not th.is_alive(), "the collector's callback waits for a lock"
    if failed:
        raise failed[0]


def test_a_collection_inside_its_own_cells_snapshot_waits_for_no_lock():
    """``Histogram.snapshot()`` allocates under the cell's lock, so a
    collection can start on a thread that holds ``host.gc{gen}``'s: the
    callback must not wait for it (it would never return, and the process
    would collect nothing from then on); the seconds reach the cell with
    the next collection."""
    obs.watch_gc()
    cell = obs.histogram("host.gc", {"gen": 0})

    def body():
        watch = obs.watch_gc()
        gc.collect(0)                   # whatever was pending before
        n, s0 = cell.count, watch.seconds
        with cell._lock:                # where snapshot() stands
            gc.collect(0)
        assert cell.count == n and watch.seconds > s0
        assert watch._pending == [(0, watch.seconds - s0)]
        gc.collect(0)
        assert cell.count == n + 2 and not watch._pending
    _finishes(body)


def test_snapshots_of_the_collectors_cell_under_a_collection_an_allocation():
    """What a scraped replica does, with the collector at its most eager:
    every container allocated inside ``snapshot()`` starts a collection."""
    obs.watch_gc()
    cell = obs.histogram("host.gc", {"gen": 0})
    threshold = gc.get_threshold()

    def body():
        n = cell.count
        gc.set_threshold(1, 1, 1)
        try:
            for _ in range(2000):
                cell.snapshot()
                obs.get_telemetry().histograms()
        finally:
            gc.set_threshold(*threshold)
        assert cell.count > n
    try:
        _finishes(body)
    finally:
        gc.set_threshold(*threshold)


def test_a_started_scheduler_and_a_trainer_watch_the_collector(decode_model):
    obs.unwatch_gc()
    n = len(gc.callbacks)
    sched = serving.DecodeScheduler(decode_model, _cfg(warmup=False))
    try:
        assert len(gc.callbacks) == n + 1
    finally:
        sched.stop()
    import inspect

    from paddle_tpu import trainer
    assert "_obs.watch_gc()" in inspect.getsource(trainer.Trainer.train)


# -- the chunk's own time -------------------------------------------------------------

def _chunk_spans(decode_model, **cfg):
    """A request decodes; three more arrive beside it.  The names and
    durations of the chunk's and the step's spans as they CLOSE, the
    requests' tokens, and how many chunks were read behind a step."""
    ring = obs.RingBufferSink(capacity=1 << 16, record_spans=True)
    obs.add_sink(ring)
    rode0 = obs.counter(D + "chunks_overlapped").value
    try:
        sched = serving.DecodeScheduler(decode_model, _cfg(**cfg))
        outs = [f.result(timeout=300) for f in _arrive_behind_a_commit(
            sched, [_prompt(9)] + [_prompt(n, n) for n in (70, 37, 90)],
            first_new=60, new=4)]
        sched.stop()
    finally:
        obs.remove_sink(ring)
    spans = [s for s in ring.spans
             if s["name"].startswith((D + "prefill", D + "step"))]
    names = [s["name"][len(D):] for s in spans]
    durs = [s["dur"] for s in spans]
    return names, durs, outs, obs.counter(D + "chunks_overlapped").value - rode0


def test_behind_and_chunk_split_the_wait_when_a_step_is_in_flight(
        decode_model):
    """With a step in flight the chunk's token is read BEHIND the step that
    is dispatched after it: ``prefill`` is paid in two parts, around the
    decode step's own spans."""
    names, durs, _, rode = _chunk_spans(decode_model)
    # the first request's own chunk found nothing in flight
    assert names[:4] == ["prefill.dispatch", "prefill.wait", "prefill.chunk",
                         "prefill"]
    assert durs[2] >= durs[0] + durs[1]
    sent = [i for i, n in enumerate(names) if n == "prefill.dispatch"][1:]
    assert len(sent) >= 10           # the later prompts' chunks ride a decode
    assert rode == len(sent)
    for i in sent:
        # as they close: the chunk goes out, step n+1 goes out behind it,
        # step n is read and committed, and then the chunk's token is read
        assert names[i:i + 9] == [
            "prefill.dispatch", "step.build", "step.dispatch", "step.wait",
            "step", "step.commit", "prefill.wait", "prefill.chunk",
            "prefill"]
        dispatch, commit, wait, chunk, whole = (
            durs[i], durs[i + 5], durs[i + 6], durs[i + 7], durs[i + 8])
        # what the iteration pays for the chunk: nothing but span edges lies
        # between the two parts and their sum
        assert dispatch + wait <= whole < dispatch + wait + 0.02
        # the chunk's own time runs from step n's readback: an upper bound
        # with the step's commit inside it
        assert chunk >= commit + wait


def test_with_no_step_in_flight_behind_opens_no_span(decode_model):
    """``kv_guard`` reads every step in the turn that sent it: the chunk is
    read before the step is sent, and no chunk rides a step."""
    names, durs, outs, rode = _chunk_spans(decode_model, kv_guard=True)
    assert rode == 0
    sent = [i for i, n in enumerate(names) if n == "prefill.dispatch"]
    assert len(sent) > 10
    for i in sent:
        assert names[i:i + 4] == ["prefill.dispatch", "prefill.wait",
                                  "prefill.chunk", "prefill"]
    assert names.count("prefill.chunk") == names.count("prefill.wait")
    assert D + "prefill.behind" not in obs.get_telemetry().histograms()
    # ... and the tokens are the pipelined loop's
    _, _, piped, _ = _chunk_spans(decode_model)
    assert all(np.array_equal(a, b) for a, b in zip(outs, piped))


def test_the_hand_off_is_staged_inside_a_span(decode_model):
    import inspect

    src = inspect.getsource(ds.DecodeScheduler)
    assert src.count('span("serving.handoff.stage")') == 2
    # one cell carries the name: the span's (a timer beside it would be
    # dropped by the exporter, which renders the histogram of a name)
    assert "serving.handoff.stage" not in obs.get_telemetry().timers()
    assert "_turn_wait_s" not in inspect.getsource(ds)
