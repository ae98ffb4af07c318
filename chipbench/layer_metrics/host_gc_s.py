"""Seconds in garbage collections, all generations and all threads: the sums
of the cells ``host.gc{gen="0|1|2"}`` (``obs.watch_gc``; a collection holds
the GIL, so the scheduler's worker stands still for it).  Over the process,
from the scheduler's start: the checks after the window are in it, and so
are the collections that converting the trace sets off (a traced run reads
about twice an untraced one: it is no measure of what the window lost)."""
from chipbench import loop_cells


def read(observed):
    return loop_cells.sum_s(*(loop_cells.labeled("host.gc", gen=g)
                              for g in range(3)))
