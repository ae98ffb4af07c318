"""100 x the (token, expert) pairs of a decode step that fall on experts held
HERE / all the pairs its router chose (the program's counters
``serving.decode.moe.pairs`` and ``.pairs_elsewhere`` over the window): 6.25
where routing is even over the sixteen holders."""
from chipbench import glm5_decode


def read(observed):
    counts = glm5_decode.step_counts(observed)
    if counts is None:
        return None
    chosen = counts["pairs"] + counts["pairs_elsewhere"]
    return 100.0 * counts["pairs"] / chosen if chosen else None
