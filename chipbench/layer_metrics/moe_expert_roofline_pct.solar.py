"""The expert weights a decode step must read (the HELD experts that took a
pair, from the program's counter ``serving.decode.moe.experts_touched``, x the
three matrices of an expert) at the chip's HBM bandwidth, as a share of
``moe_expert_decode_ms``: ``moe_expert_roofline_pct`` for a holder of a share.
Memory bound: about 3 rows an expert against 31.5 MB of weights."""
from chipbench import kanana_decode, solar_decode


def read(observed):
    ms = kanana_decode.kernel_ms(observed, kanana_decode.MOE_KERNEL)
    counts = solar_decode.step_counts(observed)
    if ms is None or counts is None:
        return None
    cfg = observed["config"]
    return kanana_decode.roofline_pct(
        observed, kanana_decode.builder(cfg).expert_bytes(
            cfg, counts["experts_touched"]), ms)
