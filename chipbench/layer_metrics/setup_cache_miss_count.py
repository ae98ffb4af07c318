"""Programs set-up compiled because the persistent cache did not hold them: the
counter ``xla.compile.cache_misses`` summed over the set-up spans.  0 in a warm
run, the number of executables in a cold one."""
from chipbench import setup_cells


def read(observed):
    return setup_cells.counter_sum("xla.compile.cache_misses")
