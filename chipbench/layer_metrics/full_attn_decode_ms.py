"""Device time of the full-attention layers' grouped-query page walk per
decode step in the traced part of the window: the custom calls named
``paged_gqa_full_attention``, every full layer's summed
(``chipbench/mellum_decode.py``)."""
from chipbench import kanana_decode, mellum_decode


def read(observed):
    return kanana_decode.kernel_ms(observed, mellum_decode.FULL_KERNEL)
