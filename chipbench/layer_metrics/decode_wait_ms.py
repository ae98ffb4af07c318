"""Mean of the cell ``serving.decode.step.wait``: the ``np.asarray`` of the
sampled tokens, i.e. how long the host is blocked on the decode program:
since PR 36 (one step in flight) what is left of the device's step once the
host has done its own work under it.  Over the process."""
from chipbench import cells


def read(observed):
    return cells.mean_ms("serving.decode.step.wait")
