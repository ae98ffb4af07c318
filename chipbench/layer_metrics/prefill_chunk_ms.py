"""Mean of the cell ``serving.decode.prefill``: one prefill chunk's program,
dispatch to readback: what a chunk adds to the iteration that carries it.
Over the process."""
from chipbench import cells


def read(observed):
    return cells.mean_ms("serving.decode.prefill")
