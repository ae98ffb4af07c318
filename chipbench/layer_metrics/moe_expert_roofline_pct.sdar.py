"""The expert weights a forward must read (the experts that took a pair, from
the program's counter ``serving.decode.moe.experts_touched``, x the three
matrices of an expert) at the chip's HBM bandwidth, as a share of the device
time of the custom calls named ``moe_grouped_matmul`` a step:
``moe_expert_roofline_pct`` for a block step (256 rows, 16 rows an expert
against 9.4 MB of its weights: memory bound)."""
from chipbench import kanana_decode, sdar_decode


def read(observed):
    counts = sdar_decode.step_counts(observed)
    if counts is None:
        return None
    return kanana_decode.roofline_pct(
        observed, sdar_decode.expert_bytes(
            observed["config"], counts["experts_touched"]),
        kanana_decode.kernel_ms(observed, kanana_decode.MOE_KERNEL))
