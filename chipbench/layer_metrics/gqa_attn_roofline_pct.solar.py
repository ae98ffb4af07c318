"""The K and V rows a decode step's softmax layer must read (every cached
position of every slot: the program's counter
``serving.decode.kv.full_tokens_read`` x 2 x 1024 x 2 B) at the chip's HBM
bandwidth, as a share of the time of the custom calls named
``paged_gqa_full_attention`` (``full_attn_decode_ms``).  The rows as the
model defines them: the whole pages the walk copies read low."""
from chipbench import kanana_decode, mellum_decode, solar_decode


def read(observed):
    ms = kanana_decode.kernel_ms(observed, mellum_decode.FULL_KERNEL)
    counts = solar_decode.step_counts(observed)
    if ms is None or counts is None:
        return None
    cfg = observed["config"]
    return kanana_decode.roofline_pct(
        observed, kanana_decode.builder(cfg).kv_bytes(
            cfg, counts["full_tokens"]), ms)
