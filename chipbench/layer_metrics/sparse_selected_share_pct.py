"""100 x selected / visible tokens of the window's decode steps, from the
program's counters ``serving.decode.sparse.selected_tokens`` and
``.visible_tokens`` (summed over slots, sparse layers and KV heads): what the
block selection leaves of the context.  A silent dense fallback reads 100."""
from chipbench import sala_decode


def read(observed):
    tokens = sala_decode.step_tokens(observed)
    return None if tokens is None else 100.0 * tokens[0] / tokens[1]
