"""Mean of the cell ``executor.run``: one whole ``Executor.run`` that replayed a
compiled entry, until it returns its LazyFetch (a call that built its entry is
``executor.first_run``).  Over the process: the window and the warm-up steps
after the compile.  The inside twin of ``dispatch_ms.train``."""
from chipbench import cells


def read(observed):
    return cells.mean_ms("executor.run")
