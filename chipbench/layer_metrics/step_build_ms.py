"""Mean of the cell ``serving.decode.step.build``: planning one decode step
(its slots, positions, lengths, seeds and page tables, as host arrays and
their uploads).  Over the process."""
from chipbench import loop_cells


def read(observed):
    return loop_cells.mean_ms("serving.decode.step.build")
