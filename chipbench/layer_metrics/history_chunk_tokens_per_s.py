"""Set-up's prompt tokens over the seconds its chunk programs took by
themselves: the sum of the cell ``serving.decode.prefill.chunk`` over the
process (a standing cell runs chunks in set-up alone).  What
``history_prefill_tokens_per_s`` read before a decode step stayed in flight
ahead of every chunk."""
from chipbench import loop_cells


def read(observed):
    chunk_s = loop_cells.sum_s("serving.decode.prefill.chunk")
    if chunk_s is None:
        return None
    tokens = (observed.get("setup") or {}).get("prompt_tokens", 0)
    return tokens / chunk_s if chunk_s else 0.0
