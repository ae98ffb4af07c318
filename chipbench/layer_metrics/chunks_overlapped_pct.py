"""Share of prefill chunks whose token was read after a decode step had been
dispatched behind them: 100 x the counter ``serving.decode.chunks_overlapped``
over ``serving.decode.prefills`` (how often the device had a step queued
while the host committed a chunk).  Over the process."""
from chipbench import loop_cells


def read(observed):
    return loop_cells.counter_ratio_pct("serving.decode.chunks_overlapped",
                                        "serving.decode.prefills")
