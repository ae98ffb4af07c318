"""Seconds constructing decode schedulers (every one of the process: an
engine's, each replica's): the sum of the cell ``serving.decode.build``, the
whole of ``DecodeScheduler.__init__`` (cache allocated, weights placed,
programs warmed)."""
from chipbench import loop_cells


def read(observed):
    return loop_cells.sum_s("serving.decode.build")
