"""Readers of the program's always-on phase cells (``paddle_tpu.observability``
histograms, one per span name; ``docs/observability.md``, "Phases").

A cell is read where the metric is read, i.e. over the PROCESS, not over the
window: a serving cell's scheduler phases cover the window plus the drain (the
engine's warm-up does not pass through the scheduler's loop), a training
cell's executor phases the window plus the warm-up steps after the compile
(calls that build their entry have cells of their own).  ``count``, ``sum``
and the mean of a cell are exact; only its quantiles are as coarse as the
buckets.  Where the program has no such span (an older commit) the cell is
empty and every reader returns ``None``: the line then leaves the metric out.
"""


def snapshot(cell):
    from paddle_tpu import observability as obs

    return obs.histogram(cell).snapshot()


def mean_ms(cell):
    s = snapshot(cell)
    return 1e3 * s.sum / s.count if s.count else None


def sum_s(*cells):
    """Seconds spent in ``cells`` together; None where none was observed."""
    snaps = [snapshot(c) for c in cells]
    if not any(s.count for s in snaps):
        return None
    return sum(s.sum for s in snaps)


def count_ratio_pct(part, whole):
    n = snapshot(whole).count
    return 100.0 * snapshot(part).count / n if n else None
