"""The bytes a perfect decode step must move (weights once, the selected K/V
and pooled keys, the lightning state read and written;
``models/minicpm_sala.py``) at the chip's HBM bandwidth, as a share of the
device time of the ``jit_decode`` program in the trace."""
from chipbench import sala_decode


def read(observed):
    ms = sala_decode.step_device_ms(observed)
    tokens = sala_decode.step_tokens(observed)
    if ms is None or tokens is None:
        return None
    cfg = observed["config"]
    model = sala_decode.builder(cfg)
    nbytes = (model.weight_bytes(cfg) + model.sparse_bytes(cfg, *tokens)
              + model.state_bytes(cfg, observed["active_slots"]))
    return sala_decode.roofline_pct(observed, nbytes, ms)
