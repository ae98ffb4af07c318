"""Seconds of set-up in the engine's warm-up and model load: the sums of the
cells ``serving.decode.warmup`` and ``serving.model_load``."""
from chipbench import cells


def read(observed):
    return cells.sum_s("serving.decode.warmup", "serving.model_load")
