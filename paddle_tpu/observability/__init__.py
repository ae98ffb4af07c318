"""Unified observability: step telemetry, trace events, pluggable sinks.

The reference Fluid shipped ``profiler.py``/``metrics.py`` as first-class
training instrumentation; its C++ runtime additionally kept global
counters (paddle/fluid/platform/profiler.cc).  This package is the
TPU-native rebuild of that idea as ONE subsystem instead of scattered
module-level counters:

- :class:`Telemetry` — a registry of named counters / gauges / timers
  with thread-safe updates (the async device-feed pipeline publishes
  from background threads) and a near-zero-overhead disabled path.
  Counters and gauges ALWAYS count: they are the single source of truth
  behind the public accessors (``executor.feed_host_copy_count()``,
  ``reader.device_prefetch.transfer_count()``), so enabling or disabling
  telemetry never changes their values — the bitwise on/off contract.
- step records — ``Executor.run`` and ``Trainer.train``/``test`` emit
  one structured dict per step (steps/s, compile vs execute time, feed
  host-copy count, prefetch transfer count, NaN-guard verdict,
  retry/rewind totals, checkpoint durations) tagged with program/run
  ids.  Records only flow when telemetry is enabled AND a sink is
  attached; otherwise the per-step cost is one attribute read.
- phases — :func:`span` is the one way to time a host-side phase (the
  scheduler's iteration, ``Executor.run``'s feed preparation / bind /
  dispatch / write-back, the prefetcher's conversion and wait,
  checkpoint IO).  It is always on: every span observes into the
  histogram cell of its name, and lies in any running ``jax.profiler``
  trace as ``paddle_tpu.<name>`` on its thread's line, on the device
  trace's clock.  With a span sink attached the same spans also export
  as Chrome ``trace_event`` JSON (:class:`~.sinks.ChromeTraceSink`), for
  hosts with no profiler session.  See docs/observability.md "Phases".
- set-up (:mod:`~.startup`) — :func:`watch_compiles` turns every jax
  compile request into spans (``xla.compile.trace`` / ``.lower`` /
  ``.backend``) and counters (requests, persistent-cache hits and misses)
  labelled with the set-up or loop span that caused it, so a process start
  accounts for its own time.  See docs/observability.md "Set-up".
- pluggable sinks (:mod:`~.sinks`) — JSONL file, in-memory ring buffer
  for tests, periodic stdout summary, Chrome-trace exporter.
- compute introspection (:mod:`~.xla_stats`) — per-compiled-program
  XLA cost/memory capture published as ``compute.*`` gauges (flops,
  bytes accessed, peak HBM, roofline verdict against a per-device peak
  table).  See docs/observability.md "Compute introspection".

``PADDLE_TPU_TELEMETRY=0`` is the process-wide killswitch: step records,
the span sinks, and the profiler's implicit stdout report all go quiet;
counters and phase cells still count.

Usage::

    from paddle_tpu import observability as obs

    sink = obs.JsonlSink("/tmp/telemetry.jsonl")
    obs.add_sink(sink)
    trainer.train(...)          # step records stream to the file
    sink.close()

    trace = obs.ChromeTraceSink("/tmp/trace.json")
    obs.add_sink(trace)
    trainer.train(...)          # host spans; load trace.json in Perfetto
    trace.close()
"""
from __future__ import annotations

from . import xla_stats
from .export import (
    MetricsServer,
    parse_prometheus,
    prometheus_name,
    render_prometheus,
)
from .histogram import Histogram, HistogramSnapshot, default_bounds
from .registry import (
    Counter,
    Frame,
    Gauge,
    Telemetry,
    Timer,
    add_sink,
    close_frame,
    counter,
    emit,
    enabled,
    gauge,
    get_telemetry,
    histogram,
    inc,
    labeled_name,
    observe,
    observe_span,
    open_frame,
    past_span,
    record_span,
    remove_sink,
    reset,
    span,
    split_labels,
    timed,
    timer,
    unwatch_gc,
    watch_gc,
)
from .sinks import (
    ChromeTraceSink,
    JsonlSink,
    RingBufferSink,
    Sink,
    StdoutSummarySink,
    print_report,
)
from .slo import SLOAlert, SLOMonitor, SLOTarget
from .startup import (
    compiles_within,
    setup_span,
    unwatch_compiles,
    watch_compiles,
)
from .tracing import TraceContext, build_trace_tree, new_trace

__all__ = [
    "Telemetry",
    "Counter",
    "Gauge",
    "Timer",
    "Histogram",
    "HistogramSnapshot",
    "default_bounds",
    "get_telemetry",
    "enabled",
    "counter",
    "gauge",
    "timer",
    "histogram",
    "labeled_name",
    "split_labels",
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
    "watch_compiles",
    "unwatch_compiles",
    "setup_span",
    "compiles_within",
    "Sink",
    "JsonlSink",
    "RingBufferSink",
    "StdoutSummarySink",
    "ChromeTraceSink",
    "print_report",
    "STEP_SCHEMA",
    "TraceContext",
    "new_trace",
    "build_trace_tree",
    "MetricsServer",
    "render_prometheus",
    "prometheus_name",
    "parse_prometheus",
    "SLOMonitor",
    "SLOTarget",
    "SLOAlert",
    "xla_stats",
]

# The step-record schema every future perf/robustness PR reports into.
# ``tools/check_observability.py`` validates JSONL sink output against it;
# keys marked required must be present in every trainer step record.
STEP_SCHEMA = {
    "required": [
        "type",            # "step"
        "ts",              # wall-clock seconds (time.time)
        "source",          # "trainer" | "executor"
        "run_id",          # opaque id tying one loop's records together
        "program",         # program tag ("<id-hex>:v<version>")
        "step",            # 0-based step index within the source's run
        "duration_s",      # wall seconds of this step
        "steps_per_s",     # 1 / duration_s
        "feed_host_copies",    # cumulative executor.feed_host_copy counter
        "prefetch_transfers",  # cumulative prefetch.transfer counter
        "nan_ok",          # True/False guard verdict, None when unguarded
    ],
    "optional": [
        "phase",           # trainer records: "train" | "test"
        "epoch",           # trainer records only
        "compile",         # True when this run built+compiled a fresh entry
        "fast_path",       # executor records: bound fast path replayed
        "nan_guard",       # guard armed for this step
        "retries",         # cumulative resilience.retry counter
        "rewinds",         # cumulative trainer nan_rewinds
        "checkpoint_save_s",  # duration, present on checkpoint steps
        "checkpoint_load_s",  # duration, present after a rewind/resume
        "metrics",         # fetched scalar metrics when cheaply available
    ],
}
