"""Device time of the delta-rule state update per decode step in the traced
part of the window: the custom calls named ``kda_state_decode``, every
delta-rule layer's summed (``chipbench/solar_decode.py``)."""
from chipbench import kanana_decode, solar_decode


def read(observed):
    return kanana_decode.kernel_ms(observed, solar_decode.STATE_KERNEL)
