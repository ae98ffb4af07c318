"""The delta-rule state a decode step must read and write (``[64, 128, 128]``
float32 each way a slot that decodes and layer: the program's counter
``serving.decode.kda.slot_updates`` x 2 x 4 MB) at the chip's HBM bandwidth,
as a share of ``kda_state_decode_ms``.  Memory bound: 7 operations an entry
against 8 bytes."""
from chipbench import kanana_decode, solar_decode


def read(observed):
    ms = kanana_decode.kernel_ms(observed, solar_decode.STATE_KERNEL)
    counts = solar_decode.step_counts(observed)
    if ms is None or counts is None:
        return None
    cfg = observed["config"]
    return kanana_decode.roofline_pct(
        observed, kanana_decode.builder(cfg).state_bytes(
            cfg, counts["slot_updates"]), ms)
