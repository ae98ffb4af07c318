"""The bytes the sparse layers of a perfect decode step must read (K and V of
the selected tokens, the pooled keys of the visible ones;
``models/minicpm_sala.py:sparse_bytes`` over the step's own token counts) at
the chip's HBM bandwidth, as a share of ``sparse_attn_decode_ms``.  Memory
bound: 4 x 16 x 128 operations a selected token against 512 bytes."""
from chipbench import sala_decode


def read(observed):
    ms = sala_decode.per_step_ms(observed, "sparse")
    tokens = sala_decode.step_tokens(observed)
    if ms is None or tokens is None:
        return None
    model = sala_decode.builder(observed["config"])
    return sala_decode.roofline_pct(
        observed, model.sparse_bytes(observed["config"], *tokens), ms)
