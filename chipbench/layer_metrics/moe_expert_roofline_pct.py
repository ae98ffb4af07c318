"""The routed-expert weights a decode step must read (the experts that took a
pair, from the program's counter ``serving.decode.moe.experts_touched``, x
the three matrices of an expert) at the chip's HBM bandwidth, as a share of
``moe_expert_decode_ms``.  The shared experts are in neither the bytes nor the
time (plain XLA matmuls: the whole step's share has them).  Memory bound:
about 3 rows an expert against 9.4 MB of weights."""
from chipbench import kanana_decode


def read(observed):
    ms = kanana_decode.kernel_ms(observed, kanana_decode.MOE_KERNEL)
    counts = kanana_decode.step_counts(observed)
    if ms is None or counts is None:
        return None
    cfg = observed["config"]
    return kanana_decode.roofline_pct(
        observed, kanana_decode.builder(cfg).expert_bytes(
            cfg, counts["experts_touched"]), ms)
