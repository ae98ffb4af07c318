"""Mean of the cell ``serving.decode.iteration.host``: an iteration's duration
less its ``*.wait`` spans (the token readbacks), i.e. host time during which
this scheduler has nothing queued on the device.  Over the process."""
from chipbench import cells


def read(observed):
    return cells.mean_ms("serving.decode.iteration.host")
