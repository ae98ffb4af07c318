"""Mean of the cell ``serving.decode.step.dispatch``: the compiled decode
step's call until jax returns the output arrays (argument handling and the
launch; the step itself runs behind it).  Over the process."""
from chipbench import loop_cells


def read(observed):
    return loop_cells.mean_ms("serving.decode.step.dispatch")
