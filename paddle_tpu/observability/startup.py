"""Set-up accounts for its own time: jax's compile requests as spans of the
registry, put down to the set-up or loop span that caused them, and the
process's age when the package has been imported.

jax reports every compile request through ``jax.monitoring``, synchronously
on the compiling thread: the trace of the Python function to a jaxpr, the
lowering of the jaxpr to a module, and the backend's compile (which encloses
the persistent cache's look-up and so is the cache's answer where it hits).
:func:`watch_compiles` turns them into the registry's own kinds (see
docs/observability.md, "Set-up"):

- spans ``xla.compile.trace`` / ``.lower`` / ``.backend``, each observed into
  the cell of its name labelled ``{within}`` and handed to the span sinks with
  jax's own start and duration (tags ``fun``, ``within``);
- counters ``xla.compile.requests{within}`` (one a backend span),
  ``xla.compile.cache_hits{within}`` / ``.cache_misses{within}``, the cell
  ``xla.compile.cache_retrieval{within}`` and the counter of seconds
  ``xla.compile.seconds_saved``.

``within`` is the name of the set-up or loop span open on the compiling
thread (:data:`SITES`), ``other`` under none of them.  The few sites that
compile say so themselves (:func:`setup_span`, :func:`compiles_within`): the
hot ``_Span`` learns nothing of it.
"""
from __future__ import annotations

import collections
import contextlib
import os
import threading
import time

from . import registry as _reg

__all__ = [
    "SITES",
    "compiles_within",
    "setup_span",
    "watch_compiles",
    "unwatch_compiles",
    "note_import",
    "process_age_s",
]

#: what a compile request is put down to: the call of a fresh executor
#: entry, a scheduler's construction and its two named parts (these four
#: are set-up's), the scheduler's loop (a shape that escaped the warmed
#: menu, or set-up's own history prefill), and whatever compiles under none
#: of them (eager ops of the caller, a benchmark's references,
#: ``decode_program_text()``)
SITES = ("executor.first_run", "serving.decode.build", "serving.model_load",
         "serving.decode.warmup", "serving.decode.iteration", "other")

TRACE, LOWER, BACKEND = ("xla.compile.trace", "xla.compile.lower",
                         "xla.compile.backend")
_SPAN_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": LOWER,
    "/jax/core/compile/backend_compile_duration": BACKEND,
}
REQUESTS, HITS, MISSES = ("xla.compile.requests", "xla.compile.cache_hits",
                          "xla.compile.cache_misses")
RETRIEVAL = "xla.compile.cache_retrieval"
_COUNT_EVENTS = {
    "/jax/compilation_cache/cache_hits": HITS,
    "/jax/compilation_cache/cache_misses": MISSES,
}
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"

# compile spans closed on a thread and not yet claimed by one that encloses
# them: a trace that is never enclosed stays, so the list is bounded (a span
# with more direct children than this counts the oldest of them twice)
_CLOSED_KEPT = 4096


class _Site(threading.local):
    within = "other"    # a thread that never said reads the class's
    closed = None       # (start, seconds) of this thread's compile spans


_site = _Site()


@contextlib.contextmanager
def compiles_within(name):
    """While the block runs, compile requests of THIS thread are put down to
    ``name`` (the innermost block wins)."""
    outer, _site.within = _site.within, name
    try:
        yield
    finally:
        _site.within = outer


@contextlib.contextmanager
def setup_span(name, **tags):
    """``obs.span(name, **tags)`` for a set-up extent that compiles: its
    name is also what :func:`watch_compiles` puts this thread's compile
    requests down to while it is open."""
    with compiles_within(name), _reg.span(name, **tags) as span:
        yield span


class _CompileWatch:
    """The three ``jax.monitoring`` listeners of :func:`watch_compiles`.
    ``calls`` counts every call jax makes to any of them: tens of thousands
    a process, for jax reports a trace (as a time span AND as a duration,
    which is dropped here) for every ``jnp`` function a traced program
    calls."""

    def __init__(self):
        tel = self._telemetry = _reg.get_telemetry()
        self.calls = 0
        # every site's cells from the start: a reader finds 0 in a process
        # that compiled nothing there, and nothing only in a program that
        # has no watcher.  The spans' cells are kept by (name, site): a
        # trace event comes ten thousand times a process
        self._spans = {}
        for site in SITES:
            labels = {"within": site}
            tel.histogram(RETRIEVAL, labels)
            for name in (TRACE, LOWER, BACKEND):
                self._spans[name, site] = tel.histogram(name, labels)
            for name in (REQUESTS, HITS, MISSES):
                tel.counter(name, labels)
        self._saved = tel.counter("xla.compile.seconds_saved")

    def on_time_span(self, event, start, end, fun_name="", **_):
        self.calls += 1
        name = _SPAN_EVENTS.get(event)
        if name is None:
            return
        site = _site
        within = site.within
        closed = site.closed
        if closed is None:
            closed = site.closed = collections.deque(maxlen=_CLOSED_KEPT)
        # SELF time: what closed on this thread inside this extent (the
        # traces of the functions a traced function calls, an eager op's
        # whole compile) has been observed already, so the cells' sums are
        # seconds of wall clock and not of nesting
        dur = own = end - start
        while closed and closed[-1][0] >= start:
            own -= closed.pop()[1]
        closed.append((start, dur))
        tel = self._telemetry
        cell = self._spans.get((name, within))
        if cell is None:        # a site of the caller's own naming
            cell = self._spans[name, within] = tel.histogram(
                name, {"within": within})
        tel.past_span(name, start, dur, cell=cell, seconds=max(own, 0.0),
                      tags={"fun": fun_name, "within": within})
        if name == BACKEND:
            tel.counter(REQUESTS, {"within": within}).inc()

    def on_event(self, event, **_):
        self.calls += 1
        name = _COUNT_EVENTS.get(event)
        if name is not None:
            self._telemetry.counter(name, {"within": _site.within}).inc()

    def on_duration(self, event, seconds, **_):
        self.calls += 1
        if event == _RETRIEVAL_EVENT:
            self._telemetry.histogram(
                RETRIEVAL, {"within": _site.within}).observe(seconds)
        elif event == _SAVED_EVENT:
            # jax's figure (the compile's seconds as the entry recorded
            # them, less the retrieval) is below 0 where reading took
            # longer than compiling had: nothing was saved there
            self._saved.inc(max(seconds, 0.0))


_watch = None
_watching = False
_watch_lock = threading.Lock()


def watch_compiles():
    """Account for jax's compile requests from here on (the module's
    docstring has the cells).  Idempotent: one time-span listener, one event
    listener and one duration listener (the cache's two timings) however
    often it is called; returns the watcher.  Called by
    ``enable_compilation_cache()`` (the first ``Executor()``) and by
    ``DecodeScheduler.__init__``; always on, like :func:`watch_gc`."""
    global _watch, _watching
    import jax.monitoring as monitoring

    with _watch_lock:
        if _watch is None:
            _watch = _CompileWatch()
        if not _watching:
            monitoring.register_event_time_span_listener(_watch.on_time_span)
            monitoring.register_event_listener(_watch.on_event)
            monitoring.register_event_duration_secs_listener(
                _watch.on_duration)
            _watching = True
        return _watch


def unwatch_compiles():
    """Take :func:`watch_compiles`'s listeners out again (tests)."""
    global _watching
    import jax.monitoring as monitoring

    with _watch_lock:
        if not _watching:
            return
        monitoring.unregister_event_time_span_listener(_watch.on_time_span)
        monitoring.unregister_event_listener(_watch.on_event)
        monitoring.unregister_event_duration_listener(_watch.on_duration)
        _watching = False


def process_age_s():
    """Seconds since this process started (the interpreter's own start-up
    included), from ``/proc``; None where there is no ``/proc``."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


def note_import(t0):
    """The end of ``import paddle_tpu`` (``t0``: ``perf_counter`` at the top
    of the package's ``__init__``): the package's own import into the cell
    ``process.import``, the process's age into the gauge
    ``process.import_done_s`` (absent where there is no ``/proc``)."""
    _reg.histogram("process.import").observe(time.perf_counter() - t0)
    age = process_age_s()
    if age is not None:
        _reg.gauge("process.import_done_s").set(age)
