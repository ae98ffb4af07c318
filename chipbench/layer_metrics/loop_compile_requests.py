"""jax compile requests from inside the scheduler's loop, over the process
(set-up's history prefill runs through the loop and is in it): the counter
``xla.compile.requests{within="serving.decode.iteration"}``.  The serving twin
of ``compiles_in_window.train``; 0 is the contract of ``warmup()``."""
from chipbench import setup_cells


def read(observed):
    return setup_cells.counter_sum("xla.compile.requests",
                                   within=(setup_cells.LOOP_SPAN,))
