"""Share of scheduler iterations that carried a prefill chunk: 100 x the count
of the cell ``serving.decode.prefill`` over the count of
``serving.decode.iteration`` (at most one chunk an iteration).  Over the
process."""
from chipbench import cells


def read(observed):
    return cells.count_ratio_pct("serving.decode.prefill",
                                 "serving.decode.iteration")
