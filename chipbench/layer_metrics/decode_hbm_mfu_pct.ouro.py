"""The bytes a perfect decode step must move (the 12 layers' weights
``total_ut_steps`` times, the head, the K and V rows read and written in all
48 K/V layers: ``chipbench/ouro_decode.py``) at the chip's HBM bandwidth, as a
share of the device time of the ``jit_decode`` program in the trace: the whole
step's share of its roofline."""
from chipbench import kanana_decode, ouro_decode


def read(observed):
    ms = kanana_decode.step_device_ms(observed)
    counts = ouro_decode.step_counts(observed)
    if ms is None or counts is None:
        return None
    return kanana_decode.roofline_pct(
        observed, ouro_decode.step_bytes(observed["config"], counts), ms)
