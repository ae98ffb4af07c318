"""Mean of the cell ``executor.prepare_feed``: checking and casting the feed
against the bound plan.  ``exe_run_ms`` less this and ``exe_launch_ms`` is
bind + write-back.  Over the process."""
from chipbench import cells


def read(observed):
    return cells.mean_ms("executor.prepare_feed")
