"""Start and stop ``jax.profiler`` for the traced sub-window of a run, and
the benchmark's own host spans (``jax.profiler.TraceAnnotation`` named
``chipbench.<what>``, also timed on the host clock for the readers)."""
from __future__ import annotations

import contextlib
import shutil
import tempfile
import time

from . import trace_reduce


class Spans:
    """Host-clock durations by span name; each span is also written into the
    profiler's trace when one is running."""

    def __init__(self):
        self.seconds = {}

    @contextlib.contextmanager
    def span(self, name):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + name):
            yield
        self.seconds.setdefault(name, []).append(time.perf_counter() - t0)


class Tracer:
    """One profiler session into a directory under ``$TMPDIR``; ``stop``
    returns the trace in trace_reduce's plain form and removes the files."""

    def __init__(self):
        self._dir = None
        self._window = None

    def start(self):
        import jax

        self._dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no per-call python events
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self._window.__enter__()

    def stop(self):
        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            return trace_reduce.load_xplane(
                trace_reduce.find_xplane(self._dir))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None
