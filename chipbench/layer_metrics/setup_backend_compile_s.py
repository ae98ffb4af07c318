"""Seconds of set-up inside the backend's compile requests: the sum of the cell
``xla.compile.backend`` over the set-up spans.  XLA's own compile where the
persistent cache missed, the cache's answer (look-up, read, deserialise) where
it hit."""
from chipbench import setup_cells


def read(observed):
    return setup_cells.span_sum_s("xla.compile.backend")
