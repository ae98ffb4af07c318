"""Device time of the EVA attention kernel per decode step in the traced part
of the window: the custom calls named ``eva_window_summary_decode`` (one walk
over the window's pages and then the summary pages, one softmax), every
layer's summed (``chipbench/eva_decode.py``)."""
from chipbench import eva_decode, kanana_decode


def read(observed):
    return kanana_decode.kernel_ms(observed, eva_decode.KERNEL)
