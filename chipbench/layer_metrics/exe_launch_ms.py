"""Mean of the cell ``executor.dispatch``: the compiled call alone, until jax
hands back the result arrays (compile steps have the cell
``executor.compile``).  Over the process."""
from chipbench import cells


def read(observed):
    return cells.mean_ms("executor.dispatch")
