"""Seconds of set-up in tracing Python functions to jaxprs and lowering them to
modules: the sums of the cells ``xla.compile.trace`` (a trace's SELF time, so
nested traces count once) and ``xla.compile.lower`` over the set-up spans
(``within`` = ``executor.first_run``, ``serving.decode.build``,
``serving.model_load``, ``serving.decode.warmup``).  The persistent cache
saves none of it."""
from chipbench import setup_cells


def read(observed):
    return setup_cells.span_sum_s("xla.compile.trace", "xla.compile.lower")
