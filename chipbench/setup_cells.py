"""Readers of set-up's own account (PR 54: ``obs.watch_compiles()`` turns
jax's compile requests into the cells ``xla.compile.trace`` / ``.lower`` /
``.backend`` / ``.cache_retrieval`` and the counters ``xla.compile.requests``
/ ``.cache_hits`` / ``.cache_misses``, each labelled ``{within}`` with the
program span that caused the request), on top of ``loop_cells.py`` and like
it over the PROCESS.

What keeps the drivers' own compiles out (the check's ``jax.jit`` of the
references, ``decode_program_text()``, which run after the window) is the
label, not a window: a request under no program span is ``within="other"``
and no reader here sums it.  The program registers every ``within`` cell when
the watcher is armed, so a reader gives a number in every run of a program
that HAS the watcher (0 where nothing was observed: a warm run's misses) and
``None`` on a program without it (an older commit): the line then leaves the
metric out.
"""
from chipbench import loop_cells

#: the spans of the program under which a compile request is set-up's
SETUP_SPANS = ("executor.first_run", "serving.decode.build",
               "serving.model_load", "serving.decode.warmup")
#: the scheduler's loop: a request there escaped the warmed menu
LOOP_SPAN = "serving.decode.iteration"


def span_sum_s(*cells, within=SETUP_SPANS):
    """Seconds in ``cells`` over the spans ``within``."""
    return loop_cells.sum_s(*(loop_cells.labeled(c, within=w)
                              for c in cells for w in within))


def counter_sum(name, within=SETUP_SPANS):
    """Counter ``name`` summed over the spans ``within``."""
    held = loop_cells._telemetry().counters()
    keys = [loop_cells.labeled(name, within=w) for w in within]
    if not all(k in held for k in keys):
        return None
    return float(sum(held[k].value for k in keys))


def gauge(name):
    g = loop_cells._telemetry().gauges().get(name)
    return None if g is None or g.value is None else float(g.value)
