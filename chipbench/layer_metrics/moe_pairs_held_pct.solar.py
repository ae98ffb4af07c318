"""100 x the (token, expert) pairs of a decode step that fall on experts held
HERE / all the pairs its router chose (the program's counters
``serving.decode.moe.pairs`` and ``.pairs_elsewhere`` over the window): 12.5
where routing is even over the eight holders."""
from chipbench import solar_decode


def read(observed):
    counts = solar_decode.step_counts(observed)
    if counts is None:
        return None
    chosen = counts["pairs"] + counts["pairs_elsewhere"]
    return 100.0 * counts["pairs"] / chosen if chosen else None
