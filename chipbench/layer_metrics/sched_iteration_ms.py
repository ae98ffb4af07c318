"""Mean of the cell ``serving.decode.iteration``: one turn of the scheduler's
loop that had active slots (admit, at most one prefill chunk, the decode step,
commit), over the process: the window and the drain."""
from chipbench import cells


def read(observed):
    return cells.mean_ms("serving.decode.iteration")
