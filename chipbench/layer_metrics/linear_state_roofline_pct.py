"""The bytes of lightning state a decode step must read and write
(``models/minicpm_sala.py:state_bytes`` for the slots that decode) at the
chip's HBM bandwidth, as a share of ``linear_state_decode_ms``."""
from chipbench import sala_decode


def read(observed):
    ms = sala_decode.per_step_ms(observed, "state")
    if ms is None:
        return None
    model = sala_decode.builder(observed["config"])
    return sala_decode.roofline_pct(
        observed, model.state_bytes(observed["config"],
                                    observed["active_slots"]), ms)
