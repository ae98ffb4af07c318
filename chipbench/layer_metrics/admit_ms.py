"""Mean of the cell ``serving.decode.admit``: queue pop, prefix look-up, page
allocation and seating at the head of an iteration (the idle wait on an empty
queue is the cell ``serving.decode.idle``, not this).  Over the process."""
from chipbench import cells


def read(observed):
    return cells.mean_ms("serving.decode.admit")
