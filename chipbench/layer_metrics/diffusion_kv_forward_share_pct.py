"""100 x the forwards that found their block whole and wrote its K/V (and
delivered nothing) / all forwards of live slots, over the window: the
program's counters ``serving.decode.diffusion.kv_forwards`` over
``.forwards``.  20 at 5 forwards a block."""
from chipbench import sdar_decode


def read(observed):
    c = sdar_decode.window_counts(observed)
    if c is None:
        return None
    return 100.0 * c[sdar_decode.PREFIX + "kv_forwards"] / c[
        sdar_decode.PREFIX + "forwards"]
