"""The indexer keys a decode step must read (every visible token of every
slot in every layer, the program's counter ``serving.decode.index.rows_scored``
x 256 B) at the chip's HBM bandwidth, as a share of ``dsa_index_decode_ms``."""
from chipbench import glm5_decode, kanana_decode


def read(observed):
    ms = glm5_decode.stage_ms(observed, "index")
    counts = glm5_decode.step_counts(observed)
    if ms is None or counts is None:
        return None
    return kanana_decode.roofline_pct(observed, glm5_decode.index_bytes(
        observed["config"], counts["rows_scored"]), ms)
