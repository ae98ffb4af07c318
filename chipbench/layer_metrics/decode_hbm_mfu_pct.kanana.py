"""The bytes a perfect decode step must move (the weights every step reads,
the routed experts that took a pair, the latent rows of every visible token:
``models/deepseek_v3.py``) at the chip's HBM bandwidth, as a share of the
device time of the ``jit_decode`` program in the trace: the whole step's
share of its roofline."""
from chipbench import kanana_decode


def read(observed):
    ms = kanana_decode.step_device_ms(observed)
    counts = kanana_decode.step_counts(observed)
    if ms is None or counts is None:
        return None
    cfg = observed["config"]
    model = kanana_decode.builder(cfg)
    nbytes = (model.weight_bytes(cfg)
              + model.expert_bytes(cfg, counts["experts_touched"])
              + model.latent_bytes(cfg, counts["tokens_read"]))
    return kanana_decode.roofline_pct(observed, nbytes, ms)
