"""Device milliseconds of one traced decode step in the lightning indexer's
score stage (the model's scope ``dsa_index``: the ``paged_index_scores`` walk
over every visible token's indexer key, every layer's summed)."""
from chipbench import glm5_decode


def read(observed):
    return glm5_decode.stage_ms(observed, "index")
