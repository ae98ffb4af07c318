"""Readers of the serving loop's own account (PR 38: the collector, what lies
between spans, the step's phases, the chunk's own time), on top of
``cells.py`` and like it over the PROCESS.

They give a number in every run of a program that HAS the cell, 0 where
nothing was observed (no collection, no chunk): a metric that vanished from
the line whenever a run was quiet could not be compared.  The program
registers these cells when its scheduler is imported; where it has none (an
older commit) a reader returns ``None`` and the line leaves the metric out.

The loop's stall cells (``serving.decode.stall``,
``serving.decode.interval{chunk}``) have NO reader here yet.  Per-layer
metrics are read in a traced run and over the process, and in a traced run
the benchmark's own tracer thread stalls the loop (it converts the trace
beside the window: 8-15 stalls, 0.4-1.2 s, 157-249 ms the longest interval in
runs that are quiet untraced; PERF.md section 6, PR 38): such a reading could
not tell a stalled run from a quiet one.  They wait for a driver that
snapshots the cells at the window's edges, outside the tracer's extent.
"""
from chipbench import cells


def _telemetry():
    from paddle_tpu import observability as obs

    return obs.get_telemetry()


def has(*names):
    """Whether the program has registered every one of these cells."""
    held = _telemetry().histograms()
    return all(n in held for n in names)


def labeled(cell, **labels):
    from paddle_tpu import observability as obs

    return obs.labeled_name(cell, labels)


def sum_s(*names):
    if not has(*names):
        return None
    return float(sum(cells.snapshot(c).sum for c in names))


def mean_ms(cell):
    if not has(cell):
        return None
    return cells.mean_ms(cell) or 0.0


def counter_ratio_pct(part, whole):
    """100 x counter ``part`` / counter ``whole``; 0 where ``whole`` is."""
    held = _telemetry().counters()
    if part not in held or whole not in held:
        return None
    n = held[whole].value
    return 100.0 * held[part].value / n if n else 0.0
