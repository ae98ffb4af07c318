"""Share of the scheduler's iterations that lies between spans: 100 x the sum
of the cell ``serving.decode.iteration.unspanned`` (an iteration less its
direct children) over the sum of ``serving.decode.iteration``.  A tripwire for
work added to the loop outside a span.  Over the process."""
from chipbench import cells, loop_cells


def read(observed):
    between = loop_cells.sum_s("serving.decode.iteration.unspanned")
    if between is None:
        return None
    whole = cells.snapshot("serving.decode.iteration").sum
    return 100.0 * between / whole if whole else 0.0
