"""Mean of the cell ``serving.decode.prefill.chunk``: the prefill chunk
program's own time, from the end of the decode step in flight ahead of it (or
from its dispatch where nothing was in flight) to its readback; what
``prefill_chunk_ms`` holds less the rest of that step.  Over the process."""
from chipbench import loop_cells


def read(observed):
    return loop_cells.mean_ms("serving.decode.prefill.chunk")
