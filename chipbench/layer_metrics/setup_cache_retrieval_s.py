"""Seconds of set-up reading executables out of the persistent cache: the sum
of the cell ``xla.compile.cache_retrieval`` over the set-up spans.  What a HIT
costs (it grows with the executables); 0 in a cold run."""
from chipbench import setup_cells


def read(observed):
    return setup_cells.span_sum_s("xla.compile.cache_retrieval")
