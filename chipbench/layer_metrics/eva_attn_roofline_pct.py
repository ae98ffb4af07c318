"""The K, V and summary rows a decode step's attention must read (every slot's
window rows so far and visible summary rows, in every layer: the program's
counters ``serving.decode.eva.window_rows_read`` + ``.summary_rows_read`` x
16 KB a layer) at the chip's HBM bandwidth, as a share of
``eva_attn_decode_ms``.  Memory bound; the last window page's rows past the
query are copied and masked, so it reads low."""
from chipbench import eva_decode, kanana_decode


def read(observed):
    ms = kanana_decode.kernel_ms(observed, eva_decode.KERNEL)
    counts = eva_decode.step_counts(observed)
    if ms is None or counts is None:
        return None
    return kanana_decode.roofline_pct(
        observed, eva_decode.attention_bytes(observed["config"], counts), ms)
