"""The K and V rows a decode step's sliding-window layers must read (the last
``sliding_window`` positions of every slot in each sliding layer: the
program's counter ``serving.decode.kv.window_tokens_read`` x 2 x 512 x 2 B) at
the chip's HBM bandwidth, as a share of ``window_attn_decode_ms``.  Memory
bound, as ``full_attn_roofline_pct``; the first page's rows before the
window are copied and masked, so they read low."""
from chipbench import kanana_decode, mellum_decode


def read(observed):
    ms = kanana_decode.kernel_ms(observed, mellum_decode.WINDOW_KERNEL)
    counts = mellum_decode.step_counts(observed)
    if ms is None or counts is None:
        return None
    cfg = observed["config"]
    return kanana_decode.roofline_pct(
        observed, kanana_decode.builder(cfg).kv_bytes(
            cfg, 0, counts["window_tokens"])[1], ms)
