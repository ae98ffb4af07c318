"""The share of a perfect decode step's bytes that is weights read a SECOND to
last time (``(total_ut_steps - 1) x`` the 12 layers' matrices) over the window,
from the program's counters and ``chipbench/ouro_decode.py``: what the loop
adds to the traffic of the same layers unlooped.  It falls through a window as
the cache grows."""
from chipbench import ouro_decode


def read(observed):
    counts = ouro_decode.step_counts(observed)
    if counts is None:
        return None
    cfg = observed["config"]
    return 100.0 * ouro_decode.reread_bytes(cfg) / ouro_decode.step_bytes(
        cfg, counts)
