"""Device time of the routed experts' grouped matrix products per decode step
in the traced part of the window: the custom calls named
``moe_grouped_matmul`` (gate-and-up and down of every expert layer summed;
the router, the sort and the shared experts are plain XLA and not in it)."""
from chipbench import kanana_decode


def read(observed):
    return kanana_decode.kernel_ms(observed, kanana_decode.MOE_KERNEL)
