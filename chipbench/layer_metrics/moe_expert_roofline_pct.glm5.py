"""The expert weights a decode step must read (the HELD experts that took a
pair, from the program's counter ``serving.decode.moe.experts_touched``, x the
three matrices of an expert: 75.5 MB) at the chip's HBM bandwidth, as a share
of the grouped product's time in the trace: ``moe_expert_roofline_pct`` for a
holder of a sixteenth.  Memory bound: under one row an expert."""
from chipbench import glm5_decode, kanana_decode


def read(observed):
    ms = kanana_decode.kernel_ms(observed, kanana_decode.MOE_KERNEL)
    counts = glm5_decode.step_counts(observed)
    if ms is None or counts is None:
        return None
    return kanana_decode.roofline_pct(observed, glm5_decode.expert_bytes(
        observed["config"], counts["experts_touched"]), ms)
