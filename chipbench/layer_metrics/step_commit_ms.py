"""Mean of the cell ``serving.decode.step.commit``: taking one read step's
tokens into the slots, retiring what finished, releasing window pages and the
step's buffers.  Over the process."""
from chipbench import loop_cells


def read(observed):
    return loop_cells.mean_ms("serving.decode.step.commit")
