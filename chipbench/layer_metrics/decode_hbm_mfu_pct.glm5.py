"""The bytes a perfect decode step must move (the weights every step reads,
the held experts that took a pair, an indexer key of every visible token, the
latent row of every SELECTED token, the rows written:
``chipbench/glm5_decode.py``) at the chip's HBM bandwidth, as a share of the
device time of the ``jit_decode`` program in the trace: the whole step's share
of its roofline."""
from chipbench import glm5_decode, kanana_decode


def read(observed):
    ms = kanana_decode.step_device_ms(observed)
    counts = glm5_decode.step_counts(observed)
    if ms is None or counts is None:
        return None
    return kanana_decode.roofline_pct(observed, glm5_decode.step_bytes(
        observed["config"], counts), ms)
