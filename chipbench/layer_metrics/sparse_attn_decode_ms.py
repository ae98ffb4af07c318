"""Device time one decode step spends in the sparse layers' block selection
and selected-page attention (both sparse layers summed), in the traced part of
the window: the step's Pallas custom call and the operations that carry a
dimension of the selection (``chipbench/sala_decode.py``)."""
from chipbench import sala_decode


def read(observed):
    return sala_decode.per_step_ms(observed, "sparse")
