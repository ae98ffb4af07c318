"""100 x selected / visible tokens of the window's decode steps, from the
program's counters ``serving.decode.sparse.selected_tokens`` and
``.visible_tokens`` (summed over slots and layers): what the indexer's
selection leaves of the context; ``index_topk`` over the mean visible length
where every context is longer than it.  A silent dense fallback reads 100."""
from chipbench import glm5_decode


def read(observed):
    counts = glm5_decode.step_counts(observed)
    if counts is None or not counts["visible"]:
        return None
    return 100.0 * counts["selected"] / counts["visible"]
