"""Seconds of set-up in ``Executor.run`` calls that built their entry (the
start-up program and each shape's first step: trace, lower, compile or cache
look-up): the sum of the cell ``executor.first_run``."""
from chipbench import cells


def read(observed):
    return cells.sum_s("executor.first_run")
