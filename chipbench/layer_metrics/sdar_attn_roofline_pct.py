"""The K and V rows a forward's attention must read (the program's counter
``serving.decode.diffusion.kv_rows_read``: every live slot's ``kv_len + B``
rows in each of the 6 layers, x 2048 B) at the chip's HBM bandwidth, as a
share of ``full_attn_decode_ms`` (the device time of the custom calls named
``paged_gqa_full_attention`` a step: here a grid step carries a slot's whole
block, 32 query rows a KV head, no stagger).  Memory bound: a block's 4 tokens share one
reading of the rows (2 x 2 x 32 x 128 x 4 operations a position against 2048
bytes is 32 operations a byte, under the chip's 240)."""
from chipbench import kanana_decode, sdar_decode


def read(observed):
    counts = sdar_decode.step_counts(observed)
    if counts is None:
        return None
    return kanana_decode.roofline_pct(
        observed, sdar_decode.kv_read_bytes(
            observed["config"], counts["kv_rows_read"]),
        kanana_decode.kernel_ms(observed, sdar_decode.WALK_KERNEL))
