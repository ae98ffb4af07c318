"""100 x the positions a decode step's sliding-window layers are entitled to
read / the positions a whole-history walk of the same layers would read
(``serving.decode.kv.window_tokens_read`` against the sliding layers x the
sum of the slots' lengths, which ``.kv.full_tokens_read`` / the full layers
is): how far the window bounds the walk.  100 where every context is under
the window."""
from chipbench import mellum_decode


def read(observed):
    counts = mellum_decode.step_counts(observed)
    if counts is None:
        return None
    cfg = observed["config"]
    n_full = mellum_decode.layers_of(cfg, "full_attention")
    n_win = mellum_decode.layers_of(cfg, "sliding_attention")
    if not n_full or not n_win:
        return None
    return 100.0 * counts["window_tokens"] / (
        n_win * counts["full_tokens"] / n_full)
