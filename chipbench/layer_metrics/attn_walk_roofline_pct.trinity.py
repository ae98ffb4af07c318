"""The K and V rows a decode step's attention must read (every cached position
in the full layer, the last ``sliding_window`` in each sliding layer, of every
slot: the counters ``serving.decode.kv.full_tokens_read`` and
``.window_tokens_read`` with ``chunk="0"``, x 4096 B a row) at the chip's HBM
bandwidth, as a share of the time of the grouped walk over BOTH page groups
(``paged_gqa_full_attention``, ``paged_gqa_window_attention``) inside the
traced decode steps.  The rows as the model defines them: the whole pages the
walk copies, and the first page's rows before the window, read low."""
from chipbench import trinity_serve as T


def read(observed):
    ms = T.kernel_ms(observed, 0, T.WALK_KERNELS)
    counts = T.program_counts(observed, 0, T.TRACED)
    if ms is None or counts is None:
        return None
    nbytes = T.kv_row_bytes(observed["config"]) * (
        counts["full_tokens"] + counts["window_tokens"])
    return T.share_pct(T.hbm_s(observed, nbytes), ms)
