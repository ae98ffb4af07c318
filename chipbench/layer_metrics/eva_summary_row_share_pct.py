"""100 x the summary rows a decode step's attention reads / all the rows it
reads (``serving.decode.eva.summary_rows_read`` against that +
``.window_rows_read``): how much of the attention's traffic is the compressed
history.  0 while every context is inside its first window; about 45 at the
mix's mean context."""
from chipbench import eva_decode


def read(observed):
    counts = eva_decode.step_counts(observed)
    if counts is None:
        return None
    return 100.0 * counts["summary_rows"] / (
        counts["summary_rows"] + counts["window_rows"])
