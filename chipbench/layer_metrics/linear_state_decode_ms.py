"""Device time one decode step spends updating the linear-attention state
(every lightning layer summed), in the traced part of the window: the
operations whose result is a ``[.., H, d, d]`` state
(``chipbench/sala_decode.py``)."""
from chipbench import sala_decode


def read(observed):
    return sala_decode.per_step_ms(observed, "state")
