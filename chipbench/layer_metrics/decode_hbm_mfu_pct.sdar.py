"""The bytes a perfect forward of the WHOLE step must move (the 6 layers'
dense weights, the experts that took a pair, the head, the K and V rows read
and the whole blocks' rows written: ``chipbench/sdar_decode.py``) at the
chip's HBM bandwidth, as a share of the device time of the ``jit_decode``
program in the trace: the whole step's share of its roofline."""
from chipbench import kanana_decode, sdar_decode


def read(observed):
    counts = sdar_decode.step_counts(observed)
    if counts is None:
        return None
    return kanana_decode.roofline_pct(
        observed, sdar_decode.step_bytes(observed["config"], counts),
        kanana_decode.step_device_ms(observed))
