"""The latent rows a decode step's attention must read (``[c | k_pe]`` of every
visible token of every slot in every layer: the program's counter
``serving.decode.latent.tokens_read`` x 576 x 2 B) at the chip's HBM
bandwidth, as a share of ``mla_decode_ms``.  Memory bound: 2 x 32 x 1088
operations a row against 1152 bytes is 60 operations a byte, under the
chip's 240."""
from chipbench import kanana_decode


def read(observed):
    ms = kanana_decode.kernel_ms(observed, kanana_decode.MLA_KERNEL)
    counts = kanana_decode.step_counts(observed)
    if ms is None or counts is None:
        return None
    cfg = observed["config"]
    return kanana_decode.roofline_pct(
        observed, kanana_decode.builder(cfg).latent_bytes(
            cfg, counts["tokens_read"]), ms)
