"""The routed-expert weights a decode step must read (the HELD experts that
took a pair, the counter ``serving.decode.moe.experts_touched{chunk="0"}``, x
the three matrices of an expert) at the chip's HBM bandwidth, as a share of
the time of the ``moe_grouped_matmul`` calls INSIDE the traced decode steps
(the chunk programs' calls of the same kernel apart).  Memory bound: about one
row an expert against 56.6 MB of weights."""
from chipbench import trinity_serve as T


def read(observed):
    ms = T.kernel_ms(observed, 0, (T.MOE_KERNEL,))
    counts = T.program_counts(observed, 0, T.TRACED)
    if ms is None or counts is None:
        return None
    return T.share_pct(T.hbm_s(observed, T.expert_bytes(
        observed["config"], counts["experts_touched"])), ms)
