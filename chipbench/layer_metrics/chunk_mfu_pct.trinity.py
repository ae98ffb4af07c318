"""The least time the chip could take for one prefill chunk of the AFMoE
family (the larger of its bytes at the HBM peak and its operations at the
bf16 peak, ``chipbench/trinity_serve.py``, from the chunk programs' own
counters between the trace's edges) as a share of the mean device time of the
``jit_chunk`` programs in the trace: the whole chunk's share of the peak that
bounds it (memory at these sizes: 8.6 GB of weights against 0.6 TFLOP)."""
from chipbench import trinity_serve as T


def read(observed):
    ms = T.program_ms(observed, 1)
    counts = T.program_counts(observed, 1, T.TRACED)
    if ms is None or counts is None:
        return None
    cfg = observed["config"]
    return T.share_pct(max(
        T.hbm_s(observed, T.program_bytes(cfg, counts, 1)),
        T.program_flops(cfg, counts, 1) / observed["peak"]("bf16_flops")), ms)
