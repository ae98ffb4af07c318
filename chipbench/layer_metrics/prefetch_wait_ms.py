"""Mean of the cell ``prefetch.wait``: the consumer's block in ``next()`` on the
prefetched feed.  Over the process.  The inside twin of ``feed_wait_ms``."""
from chipbench import cells


def read(observed):
    return cells.mean_ms("prefetch.wait")
