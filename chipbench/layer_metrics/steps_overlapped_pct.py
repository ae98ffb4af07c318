"""Share of decode steps dispatched while the step before was still unread:
100 x the counter ``serving.decode.steps_overlapped`` over
``serving.decode.steps`` (how often the one-step pipeline was engaged).  Over
the process."""
from chipbench import loop_cells


def read(observed):
    return loop_cells.counter_ratio_pct("serving.decode.steps_overlapped",
                                        "serving.decode.steps")
