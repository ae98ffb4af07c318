"""The K and V rows a decode step's full-attention layers must read (every
cached position of every slot in each full layer: the program's counter
``serving.decode.kv.full_tokens_read`` x 2 x 512 x 2 B) at the chip's HBM
bandwidth, as a share of ``full_attn_decode_ms``.  Memory bound: 2 x 2 x 32 x
128 operations a position against 2048 bytes is 8 operations a byte, under
the chip's 240.  The rows as the model defines them: the whole pages the walk
copies and its exact-part passes read low."""
from chipbench import kanana_decode, mellum_decode


def read(observed):
    ms = kanana_decode.kernel_ms(observed, mellum_decode.FULL_KERNEL)
    counts = mellum_decode.step_counts(observed)
    if ms is None or counts is None:
        return None
    cfg = observed["config"]
    return kanana_decode.roofline_pct(
        observed, kanana_decode.builder(cfg).kv_bytes(
            cfg, counts["full_tokens"], 0)[0], ms)
