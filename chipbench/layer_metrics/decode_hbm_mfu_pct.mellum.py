"""The bytes a perfect decode step must move (the weights every step reads,
the experts that took a pair, the K and V rows each kind of layer is entitled
to: ``models/mellum.py``) at the chip's HBM bandwidth, as a share of the
device time of the ``jit_decode`` program in the trace: the whole step's
share of its roofline."""
from chipbench import kanana_decode, mellum_decode


def read(observed):
    ms = kanana_decode.step_device_ms(observed)
    counts = mellum_decode.step_counts(observed)
    if ms is None or counts is None:
        return None
    cfg = observed["config"]
    model = kanana_decode.builder(cfg)
    nbytes = (model.weight_bytes(cfg)
              + model.expert_bytes(cfg, counts["experts_touched"])
              + sum(model.kv_bytes(cfg, counts["full_tokens"],
                                   counts["window_tokens"])))
    return kanana_decode.roofline_pct(observed, nbytes, ms)
