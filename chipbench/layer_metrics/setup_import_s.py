"""Seconds from the process's start (``/proc``: the interpreter's own start-up
and whatever the entry point imported before it included) to the end of
``import paddle_tpu``: the gauge ``process.import_done_s``."""
from chipbench import setup_cells


def read(observed):
    return setup_cells.gauge("process.import_done_s")
