"""The bytes a perfect decode step must move (the weights every step reads,
the held experts that took a pair, the K and V rows the softmax layer is
entitled to, the delta-rule state read and written, the convolution's inputs:
``models/solar_open2.py:step_bytes``, from the step's own counters) at the
chip's HBM bandwidth, as a share of the device time of the ``jit_decode``
program in the trace: the whole step's share of the peak that bounds it
(memory: the step is 0.5 operations a byte)."""
from chipbench import kanana_decode, solar_decode


def read(observed):
    ms = kanana_decode.step_device_ms(observed)
    counts = solar_decode.step_counts(observed)
    if ms is None or counts is None:
        return None
    cfg = observed["config"]
    return kanana_decode.roofline_pct(
        observed, kanana_decode.builder(cfg).step_bytes(cfg, counts), ms)
