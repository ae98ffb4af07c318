"""Telemetry registry: named counters / gauges / timers + span/record fanout.

Design constraints (see docs/observability.md for the measured numbers):

- **Counters always count.**  They back load-bearing public accessors
  (``feed_host_copy_count``, ``transfer_count``) whose values are part of
  tested contracts — toggling telemetry must never change them.  An
  increment is one lock acquire + int add (~100ns), paid identically on
  and off.
- **Phases always time.**  :meth:`Telemetry.span` is the ONE way to time
  a phase and extends the counters' contract to durations: every span
  observes into the histogram cell of its name (one ``perf_counter``
  pair + one locked increment), sink or no sink, so ``count``/``sum``/
  ``mean`` of a phase are exact and phase means add up.  It also enters
  a ``jax.profiler.TraceAnnotation("paddle_tpu.<name>")``, so whenever a
  ``jax.profiler`` session runs the phase lies on its thread's line of
  ``/host:CPU`` in the same ``.xplane.pb`` as the device, on one clock;
  with no session that is one static TraceMe check (~0.02us).
- **Records and sink fan-out are gated.**  Step records and the span
  sinks cost one attribute read when disabled or sink-less
  (``telemetry.recording`` / ``_span_sinks``).
  ``PADDLE_TPU_TELEMETRY=0`` forces that quiet path; cells still count.
- **Thread-safe.**  The async device-feed pipeline publishes counters
  and spans from its transfer thread(s); every mutable structure here is
  lock-protected.  Metric objects are created once and mutated in place,
  so a module that cached ``counter("x")`` and the registry's own lookup
  always observe the same cell — ``reset()`` zeroes in place instead of
  replacing objects.
"""
from __future__ import annotations

import contextlib
import gc
import os
import threading
import time

from .histogram import Histogram

__all__ = [
    "Counter",
    "Gauge",
    "Timer",
    "Histogram",
    "histogram",
    "labeled_name",
    "split_labels",
    "Telemetry",
    "get_telemetry",
    "enabled",
    "counter",
    "gauge",
    "timer",
    "inc",
    "observe",
    "span",
    "record_span",
    "past_span",
    "timed",
    "observe_span",
    "emit",
    "reset",
    "add_sink",
    "remove_sink",
    "Frame",
    "open_frame",
    "close_frame",
    "watch_gc",
    "unwatch_gc",
]


def _env_enabled():
    return os.environ.get("PADDLE_TPU_TELEMETRY", "1") != "0"


def _safe_label_value(value):
    """Label values land verbatim inside ``name{k="v"}`` registry keys
    (and from there in the Prometheus exposition), so characters that
    would break the sample grammar — quotes, backslashes, newlines —
    are replaced instead of escaped: the strict parser we gate the
    exposition with reads no escape sequences."""
    s = str(value)
    return "".join(c if (c.isalnum() or c in "_.:/-@ ") else "_" for c in s)


def labeled_name(name, labels=None):
    """Canonical registry key for a labeled metric cell:
    ``name{k="v",...}`` with keys sorted, or ``name`` unchanged when
    ``labels`` is empty/None.  The same (name, labels) pair always maps
    to the same key, so cached handles and registry lookups agree."""
    if not labels:
        return name
    parts = ['%s="%s"' % (k, _safe_label_value(labels[k]))
             for k in sorted(labels)]
    return "%s{%s}" % (name, ",".join(parts))


def split_labels(key):
    """Inverse of :func:`labeled_name` as far as rendering needs:
    ``(base_name, label_suffix)`` where the suffix is ``""`` or the
    verbatim ``{k="v",...}`` part.  The exporter groups cells into one
    Prometheus family per base name with this."""
    i = key.find("{")
    if i < 0:
        return key, ""
    return key[:i], key[i:]


class Counter:
    """Monotonic named count; ``inc`` is safe from any thread."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name):
        self.name = name
        self._value = 0
        self._lock = threading.Lock()

    def inc(self, n=1):
        with self._lock:
            self._value += n

    @property
    def value(self):
        return self._value

    def _reset(self):
        with self._lock:
            self._value = 0

    def __repr__(self):
        return "Counter(%r, %d)" % (self.name, self._value)


class Gauge:
    """Last-written named value (e.g. queue depth, steps/s)."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name):
        self.name = name
        self._value = None
        self._lock = threading.Lock()

    def set(self, value):
        with self._lock:
            self._value = value

    @property
    def value(self):
        return self._value

    def _reset(self):
        with self._lock:
            self._value = None

    def __repr__(self):
        return "Gauge(%r, %r)" % (self.name, self._value)


class Timer:
    """Named duration aggregate with the reference profiler's report
    stats (calls / total / avg / min / max).  Running aggregates, not a
    sample list: a timer on an always-on path (checkpoint IO) must hold
    O(1) memory over an arbitrarily long training job.  Updates happen
    under a lock so report formatting never races a recording thread."""

    __slots__ = ("name", "_count", "_total", "_min", "_max", "_lock")

    def __init__(self, name):
        self.name = name
        self._count = 0
        self._total = 0.0
        self._min = None
        self._max = None
        self._lock = threading.Lock()

    def observe(self, seconds):
        s = float(seconds)
        with self._lock:
            self._count += 1
            self._total += s
            if self._min is None or s < self._min:
                self._min = s
            if self._max is None or s > self._max:
                self._max = s

    @contextlib.contextmanager
    def time(self):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.observe(time.perf_counter() - t0)

    @property
    def count(self):
        return self._count

    @property
    def total(self):
        return self._total

    def stats(self):
        """(calls, total, avg, min, max) or None when empty."""
        with self._lock:
            if not self._count:
                return None
            return (self._count, self._total, self._total / self._count,
                    self._min, self._max)

    def _reset(self):
        with self._lock:
            self._count = 0
            self._total = 0.0
            self._min = None
            self._max = None

    def __repr__(self):
        return "Timer(%r, n=%d)" % (self.name, self._count)


#: prefix of every span's name in a ``jax.profiler`` trace
TRACE_PREFIX = "paddle_tpu."

_annotation = None      # jax.profiler.TraceAnnotation, resolved at first use


class _NoAnnotation:
    """Stands in for ``TraceAnnotation`` where jax is not importable:
    never enabled, so never constructed."""

    @staticmethod
    def is_enabled():
        return False


def _resolve_annotation():
    """``jax.profiler.TraceAnnotation``, lazily: the package must import
    without jax, and cells and sinks work without it."""
    global _annotation
    try:
        from jax.profiler import TraceAnnotation
    except Exception:
        TraceAnnotation = _NoAnnotation
    _annotation = TraceAnnotation
    return TraceAnnotation


class Frame:
    """What one thread's spans add up to while the frame is open on it
    (:func:`open_frame`): every span that closes there adds its duration
    under its name, so a loop can account for its own time without a
    second clock.  ``depth`` counts the spans open on the thread; the
    span opened first under the frame (the loop's turn) closes at depth
    0, its direct children at depth 1.  ``children_s`` and ``wait_s`` are
    running totals (the depth-1 spans; the spans named ``*.wait``, which
    never nest in one another): a loop reads them at both ends of a turn.
    ``phases`` is ``{name: [seconds, spans, depth]}`` since the last
    :meth:`cut`.  A span that closes into no cell is in neither."""

    __slots__ = ("phases", "depth", "children_s", "wait_s")

    def __init__(self):
        self.phases = {}
        self.depth = 0
        self.children_s = 0.0
        self.wait_s = 0.0

    def add(self, name, dur, depth):
        entry = self.phases.get(name)
        if entry is None:
            self.phases[name] = [dur, 1, depth]
        else:
            entry[0] += dur
            entry[1] += 1
        if depth == 1:
            self.children_s += dur
        if name.endswith(".wait"):
            self.wait_s += dur

    def cut(self):
        """The phases gathered so far; the frame starts over."""
        phases, self.phases = self.phases, {}
        return phases


class _Frames(threading.local):
    frame = None        # a thread with no frame reads the class's None


_frames = _Frames()


def open_frame():
    """Open a :class:`Frame` on the calling thread (in place of any that
    was open) and return it."""
    frame = _frames.frame = Frame()
    return frame


def close_frame():
    _frames.frame = None


class _Span:
    """One timed phase (see :meth:`Telemetry.span`).  ``name`` may be
    reassigned before the block exits: the span then closes into that
    cell (``executor.run`` -> ``executor.first_run``), or into none when
    set to None (the extent stays in a running profiler trace under the
    name it was opened with).  ``duration`` is set on exit.  ``tags`` None
    (the collector's span alone) closes it into the thread's frame and
    nothing else: no cell and no sink, so no lock (:class:`_GcWatch`).
    A phase that a thread pays for in two parts (:meth:`hold`) is entered
    twice and closes once, with the sum of its extents."""

    __slots__ = ("name", "tags", "duration", "_telemetry", "_t0", "_ann",
                 "_frame", "_held", "_hold")

    def __init__(self, telemetry, name, tags):
        self._telemetry = telemetry
        self.name = name
        self.tags = tags
        self.duration = None
        self._held = 0.0        # what the extents before this one took
        self._hold = False

    def hold(self):
        """The block under way SUSPENDS the span in place of closing it: its
        exit ends the profiler's annotation and leaves the frame's depth as
        it found it, and no cell, frame or sink hears of it.  Entered again,
        the span goes on, and closes with the sum of its extents (to a span
        sink it then starts that long before its end)."""
        self._hold = True

    def __enter__(self):
        # TraceMe's own inactive path, hoisted: with no profiler session
        # running no annotation is built at all (one static call)
        cls = _annotation or _resolve_annotation()
        if cls.is_enabled():
            ann = self._ann = cls(TRACE_PREFIX + self.name)
            ann.__enter__()
        else:
            self._ann = None
        # the thread's frame, if one is open: one attribute read otherwise
        frame = self._frame = _frames.frame
        if frame is not None:
            frame.depth += 1
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = self.duration = self._held + time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        name = self.name
        frame = self._frame
        if self._hold:
            self._hold, self._held = False, dur
            name = None
        if frame is not None:
            frame.depth -= 1
            if name is not None:
                frame.add(name, dur, frame.depth)
        if name is not None and self.tags is not None:
            tel = self._telemetry
            cell = tel._histograms.get(name)
            (cell or tel.histogram(name)).observe(dur)
            if tel._span_sinks:
                tel._emit_span(name, time.time() - dur, dur,
                               threading.current_thread(), self.tags)
        return False


class Telemetry:
    """Registry + sink fanout.  One process-wide instance
    (:func:`get_telemetry`) serves the whole runtime; tests may build
    private instances."""

    def __init__(self, enabled=None):
        self._enabled = _env_enabled() if enabled is None else bool(enabled)
        self._lock = threading.Lock()       # registry structure
        self._sink_lock = threading.Lock()  # sink list + fanout
        self._counters = {}
        self._gauges = {}
        self._timers = {}
        self._histograms = {}
        self._sinks = []
        # precomputed fast-path flags: one attribute read on the hot path
        self.recording = False      # enabled and >=1 sink takes records
        self._span_sinks = ()       # sinks that take spans
        self._record_sinks = ()     # sinks that take records

    # -- enablement ----------------------------------------------------------
    @property
    def enabled(self):
        return self._enabled

    def configure(self, enabled=None):
        """Override the env-derived enablement (None = re-read the env)."""
        with self._sink_lock:  # _refresh_flags races add/remove otherwise
            self._enabled = _env_enabled() if enabled is None else bool(enabled)
            self._refresh_flags()
        return self._enabled

    def _refresh_flags(self):
        sinks = tuple(self._sinks) if self._enabled else ()
        self._span_sinks = tuple(
            s for s in sinks if getattr(s, "wants_spans", False))
        self._record_sinks = tuple(
            s for s in sinks if getattr(s, "wants_records", True))
        self.recording = bool(self._record_sinks)

    # -- metrics -------------------------------------------------------------
    # ``labels`` (a {key: value} dict) keys a DISTINCT cell per label
    # combination under one logical family: the registry key is
    # ``labeled_name(name, labels)``, reset(prefix=name) still matches
    # every labeled cell (the key starts with the base name), and the
    # exporter regroups the cells into one Prometheus family with
    # per-sample label suffixes.  Unlabeled and labeled cells of the
    # same name coexist (the unlabeled one is the cross-label
    # aggregate the SLO monitor windows over).
    def counter(self, name, labels=None) -> Counter:
        name = labeled_name(name, labels)
        c = self._counters.get(name)
        if c is None:
            with self._lock:
                c = self._counters.setdefault(name, Counter(name))
        return c

    def gauge(self, name, labels=None) -> Gauge:
        name = labeled_name(name, labels)
        g = self._gauges.get(name)
        if g is None:
            with self._lock:
                g = self._gauges.setdefault(name, Gauge(name))
        return g

    def timer(self, name, labels=None) -> Timer:
        name = labeled_name(name, labels)
        t = self._timers.get(name)
        if t is None:
            with self._lock:
                t = self._timers.setdefault(name, Timer(name))
        return t

    def histogram(self, name, labels=None) -> Histogram:
        name = labeled_name(name, labels)
        h = self._histograms.get(name)
        if h is None:
            with self._lock:
                h = self._histograms.setdefault(name, Histogram(name))
        return h

    def inc(self, name, n=1):
        self.counter(name).inc(n)

    def observe(self, name, seconds):
        self.timer(name).observe(seconds)

    def counters(self):
        with self._lock:
            return dict(self._counters)

    def gauges(self):
        with self._lock:
            return dict(self._gauges)

    def timers(self):
        with self._lock:
            return dict(self._timers)

    def histograms(self):
        with self._lock:
            return dict(self._histograms)

    def reset(self, prefix=None):
        """Zero metrics IN PLACE (cached handles stay valid).  With a
        ``prefix``, only matching names reset — ``reset_profiler`` clears
        the profiler namespace without touching e.g. the executor's
        feed-copy contract counter."""
        with self._lock:
            groups = (self._counters, self._gauges, self._timers,
                      self._histograms)
        for group in groups:
            for name, metric in list(group.items()):
                if prefix is None or name.startswith(prefix):
                    metric._reset()

    # -- sinks ---------------------------------------------------------------
    def add_sink(self, sink):
        with self._sink_lock:
            if sink not in self._sinks:
                self._sinks.append(sink)
            self._refresh_flags()
        return sink

    def remove_sink(self, sink):
        with self._sink_lock:
            if sink in self._sinks:
                self._sinks.remove(sink)
            self._refresh_flags()

    def sinks(self):
        with self._sink_lock:
            return list(self._sinks)

    # -- records / spans -----------------------------------------------------
    def emit(self, record):
        """Fan a structured record out to every record sink.  Callers gate
        on ``self.recording`` so the disabled path never builds the dict;
        the sink tuple is precomputed by add/remove_sink so the hot path
        takes no lock."""
        for s in self._record_sinks:
            try:
                s.emit(record)
            except Exception:
                # a broken sink (full disk, closed file) must never
                # take the training loop down with it
                pass

    def span(self, name, **tags):
        """Context manager timing one phase — the one primitive.  It
        ALWAYS observes the duration into the histogram cell ``name``,
        enters ``jax.profiler.TraceAnnotation("paddle_tpu." + name)`` so
        a running profiler session shows the phase on this thread's
        line beside the device, and feeds the span sinks when one is
        attached (``tags`` ride along; trace ids come from the caller's
        :class:`~.tracing.TraceContext`).  Nested spans nest: a parent's
        duration covers its children's."""
        return _Span(self, name, tags)

    #: alias of :meth:`span`, for call sites spelled ``timed(...)``
    timed = span

    def span_active(self):
        return bool(self._span_sinks)

    def record_span(self, name, ts, dur, tags=None, thread=None):
        """Emit an already-measured span (``ts`` = wall-clock start
        seconds, ``dur`` seconds) to the span sinks only — per-request
        roots and leaves assembled after the fact, which are no phase of
        a thread and feed no cell."""
        if not self._span_sinks:
            return
        self._emit_span(name, ts, dur,
                        thread or threading.current_thread(), tags or {})

    def past_span(self, name, ts, dur, labels=None, tags=None, seconds=None,
                  cell=None):
        """A span of the CALLING thread that somebody else measured and
        reports after the fact (jax's compile events: ``ts`` = wall-clock
        start, ``dur`` seconds).  Like a span that closes here, it observes
        into the cell ``name{labels}`` (``cell``, where the caller holds it
        already), adds to the thread's frame (under the cell's name, a child
        of whatever is open) and feeds the span sinks; ``seconds`` is what
        the cell and the frame get where that is not the whole extent (a
        span's SELF time, its children observed apart)."""
        if cell is None:
            cell = self.histogram(name, labels)
        s = dur if seconds is None else seconds
        cell.observe(s)
        frame = _frames.frame
        if frame is not None:
            frame.add(cell.name, s, frame.depth)
        if self._span_sinks:
            self._emit_span(name, ts, dur, threading.current_thread(),
                            tags or {})

    def observe_span(self, name, wall0, t0, tags=None):
        """The tail half of :meth:`span` for hand-timed sites whose
        control flow doesn't fit a with-block (multi-exit loops):
        observes ``perf_counter() - t0`` into the SAME cell ``name`` and
        feeds the span sinks a span starting at wall-clock ``wall0``.
        It cannot annotate a profiler trace after the fact.  Returns the
        duration."""
        dur = time.perf_counter() - t0
        self.histogram(name).observe(dur)
        if self._span_sinks:
            self._emit_span(name, wall0, dur,
                            threading.current_thread(), tags or {})
        return dur

    def _emit_span(self, name, ts, dur, thread, tags):
        for s in self._span_sinks:
            try:
                s.emit_span(name, ts, dur, thread, tags)
            except Exception:
                pass


_global = Telemetry()


def get_telemetry() -> Telemetry:
    return _global


def enabled():
    return _global.enabled


def counter(name, labels=None) -> Counter:
    return _global.counter(name, labels)


def gauge(name, labels=None) -> Gauge:
    return _global.gauge(name, labels)


def timer(name, labels=None) -> Timer:
    return _global.timer(name, labels)


def histogram(name, labels=None) -> Histogram:
    return _global.histogram(name, labels)


def inc(name, n=1):
    _global.inc(name, n)


def observe(name, seconds):
    _global.observe(name, seconds)


def span(name, **tags):
    return _global.span(name, **tags)


def record_span(name, ts, dur, tags=None, thread=None):
    _global.record_span(name, ts, dur, tags, thread)


timed = span


def past_span(name, ts, dur, labels=None, tags=None, seconds=None, cell=None):
    _global.past_span(name, ts, dur, labels, tags, seconds, cell)


def observe_span(name, wall0, t0, tags=None):
    return _global.observe_span(name, wall0, t0, tags)


def emit(record):
    _global.emit(record)


def reset(prefix=None):
    _global.reset(prefix)


class _GcWatch:
    """The ``gc.callbacks`` entry of :func:`watch_gc`: a collection is a
    span ``host.gc`` on the collecting thread, observed into the cell
    ``host.gc{gen="0|1|2"}``; ``seconds`` is the running total over all
    threads (whoever collects holds the GIL, so every thread stands still
    for it).

    A collection starts wherever its thread allocates or passes the eval
    breaker: inside a sink's ``with self._lock:``, or inside ``snapshot()``
    of the very cell it is about to close into.  So the callback WAITS FOR
    NO LOCK: the span goes to no sink and closes into no cell by itself
    (``tags`` None), and its seconds go to the cell through
    ``Histogram.observe(wait=False)``; where another thread, or this one,
    holds the cell they are kept in ``_pending`` until the next collection
    finds it free.  (A callback that blocked there would never return: the
    collector's ``collecting`` flag would stay set and the process would
    collect nothing from then on.)"""

    def __init__(self):
        self.seconds = 0.0
        self._span = None
        self._pending = []          # (generation, seconds) not yet in a cell
        # made here, so that a collection never takes the registry's lock
        self._cells = tuple(
            _global.histogram("host.gc", {"gen": g}) for g in range(3))

    def __call__(self, phase, info):
        if phase == "start":
            sp = self._span = _Span(_global, "host.gc", None)
            sp.__enter__()
            return
        sp, self._span = self._span, None
        if sp is None:
            return
        gen = info["generation"]
        sp.name = self._cells[gen].name     # the frame's name for it
        sp.__exit__(None, None, None)
        self.seconds += sp.duration
        # collections run one at a time (the collector's own flag), so
        # nothing else touches the list
        pending = self._pending
        pending.append((gen, sp.duration))
        while pending and self._cells[pending[-1][0]].observe(
                pending[-1][1], wait=False):
            pending.pop()


_gc_watch = None
_gc_lock = threading.Lock()


def watch_gc():
    """Time the collector: from here on every garbage collection is a span
    (cell ``host.gc{gen=...}``, annotation ``paddle_tpu.host.gc`` on the
    collecting thread's line of a running profiler trace).  Idempotent;
    returns the watcher, whose ``seconds`` is the running total."""
    global _gc_watch
    with _gc_lock:
        if _gc_watch is None:
            _gc_watch = _GcWatch()
        if _gc_watch not in gc.callbacks:
            gc.callbacks.append(_gc_watch)
        return _gc_watch


def unwatch_gc():
    """Take :func:`watch_gc`'s callback out again (tests)."""
    with _gc_lock:
        if _gc_watch is not None and _gc_watch in gc.callbacks:
            gc.callbacks.remove(_gc_watch)


def add_sink(sink):
    return _global.add_sink(sink)


def remove_sink(sink):
    _global.remove_sink(sink)
