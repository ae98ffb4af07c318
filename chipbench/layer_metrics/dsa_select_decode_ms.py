"""Device milliseconds of one traced decode step in the selection stage (the
model's scope ``dsa_select``: the threshold search over the index scores, the
ties by position and the compaction to a row list, every layer's summed)."""
from chipbench import glm5_decode


def read(observed):
    return glm5_decode.stage_ms(observed, "select")
