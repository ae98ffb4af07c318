"""The latent rows a decode step's attention must read (every SELECTED token
of every slot in every layer, the program's counter
``serving.decode.sparse.selected_tokens`` x 1152 B, counted once whatever
gathers them) at the chip's HBM bandwidth, as a share of
``mla_rows_decode_ms``."""
from chipbench import glm5_decode, kanana_decode


def read(observed):
    ms = glm5_decode.stage_ms(observed, "rows")
    counts = glm5_decode.step_counts(observed)
    if ms is None or counts is None:
        return None
    return kanana_decode.roofline_pct(observed, glm5_decode.rows_bytes(
        observed["config"], counts["selected"]), ms)
