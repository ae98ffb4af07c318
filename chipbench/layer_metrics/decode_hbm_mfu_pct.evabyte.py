"""The bytes a perfect decode step must move (the weights every step reads,
the window and summary rows its attention is entitled to, the rows it writes
and pools: ``chipbench/eva_decode.py``) at the chip's HBM bandwidth, as a
share of the device time of the ``jit_decode`` program in the trace: the whole
step's share of its roofline."""
from chipbench import eva_decode, kanana_decode


def read(observed):
    ms = kanana_decode.step_device_ms(observed)
    counts = eva_decode.step_counts(observed)
    if ms is None or counts is None:
        return None
    cfg = observed["config"]
    return kanana_decode.roofline_pct(
        observed, eva_decode.weight_bytes(cfg)
        + eva_decode.attention_bytes(cfg, counts)
        + eva_decode.written_bytes(cfg, counts), ms)
