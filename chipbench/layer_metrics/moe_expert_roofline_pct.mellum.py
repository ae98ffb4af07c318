"""The expert weights a decode step must read (the experts that took a pair,
from the program's counter ``serving.decode.moe.experts_touched``, x the
three matrices of an expert) at the chip's HBM bandwidth, as a share of
``moe_expert_decode_ms``: ``moe_expert_roofline_pct`` for a family whose
every layer is experts and whose configuration has no ``n_routed_experts``.
Memory bound: about 8 rows an expert against 12.4 MB of weights."""
from chipbench import kanana_decode, mellum_decode


def read(observed):
    ms = kanana_decode.kernel_ms(observed, kanana_decode.MOE_KERNEL)
    counts = mellum_decode.step_counts(observed)
    if ms is None or counts is None:
        return None
    cfg = observed["config"]
    return kanana_decode.roofline_pct(
        observed, kanana_decode.builder(cfg).expert_bytes(
            cfg, counts["experts_touched"]), ms)
