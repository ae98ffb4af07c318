"""Device time of the page walk per decode step in the traced part of the
window: the 12 custom calls in the body of the program's loop, each run
``total_ut_steps`` times a step (48 calls a step), summed
(``chipbench/ouro_decode.py``)."""
from chipbench import ouro_decode


def read(observed):
    return ouro_decode.walk_ms(observed)
