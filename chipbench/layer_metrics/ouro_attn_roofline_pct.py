"""The K and V rows a decode step's attention must read (every cached position
of every slot in each of the ``total_ut_steps x num_hidden_layers`` K/V
layers: the program's counter ``serving.decode.ut.kv_rows_read`` x 2 x 2048 x
2 B = 8192 B a row) at the chip's HBM bandwidth, as a share of
``ouro_attn_decode_ms``.  Memory bound: 2 x 2 x 16 x 128 operations a position
against 8192 bytes is 1 operation a byte, under the chip's 240.  The rows as
the model defines them: the whole pages the walk copies read low."""
from chipbench import kanana_decode, ouro_decode


def read(observed):
    ms = ouro_decode.walk_ms(observed)
    counts = ouro_decode.step_counts(observed)
    if ms is None or counts is None:
        return None
    return kanana_decode.roofline_pct(
        observed, ouro_decode.kv_bytes(
            observed["config"], counts["kv_rows_read"], 0), ms)
