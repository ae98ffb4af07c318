"""Device time of the absorbed latent-attention kernel per decode step in the
traced part of the window: the custom calls named ``paged_mla_attention``,
every layer's summed (``chipbench/kanana_decode.py``)."""
from chipbench import kanana_decode


def read(observed):
    return kanana_decode.kernel_ms(observed, kanana_decode.MLA_KERNEL)
