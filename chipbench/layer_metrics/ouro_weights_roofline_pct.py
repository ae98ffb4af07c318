"""Everything of a decode step that is not the page walk (the ``jit_decode``
program's device time less ``ouro_attn_decode_ms``): the bytes of weights a
perfect step reads (the 12 layers' matrices ``total_ut_steps`` times and the
head once: ``chipbench/ouro_decode.py``) at the chip's HBM bandwidth, as a
share of that time - the share of the peak at which weights are read AGAIN,
with 8 rows a product."""
from chipbench import kanana_decode, ouro_decode


def read(observed):
    walk = ouro_decode.walk_ms(observed)
    step = kanana_decode.step_device_ms(observed)
    if walk is None or step is None or step <= walk:
        return None
    return kanana_decode.roofline_pct(
        observed, ouro_decode.weight_bytes(observed["config"]), step - walk)
