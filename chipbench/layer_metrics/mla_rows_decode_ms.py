"""Device milliseconds of one traced decode step in the attention over the
selected rows (the model's scope ``mla_rows``: the gather of the listed rows
through the page table and the absorbed walk over them, every layer's
summed)."""
from chipbench import glm5_decode


def read(observed):
    return glm5_decode.stage_ms(observed, "rows")
