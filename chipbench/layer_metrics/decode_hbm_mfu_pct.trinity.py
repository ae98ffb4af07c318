"""The bytes a perfect decode step of the AFMoE family must move (the weights
every step reads, the held experts that took a pair, the K and V rows both
page groups' layers are entitled to, the head: ``chipbench/trinity_serve.py``,
from the decode steps' own counters between the trace's edges) at the chip's
HBM bandwidth, as a share of the mean device time of the ``jit_decode``
programs in the trace: the whole step's share of the peak that bounds it (memory)."""
from chipbench import trinity_serve as T


def read(observed):
    ms = T.program_ms(observed, 0)
    counts = T.program_counts(observed, 0, T.TRACED)
    if ms is None or counts is None:
        return None
    cfg = observed["config"]
    return T.share_pct(T.hbm_s(observed, T.program_bytes(
        cfg, counts, cfg["slots"])), ms)
