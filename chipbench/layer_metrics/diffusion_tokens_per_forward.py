"""Positions unmasked a forward a live slot over the window: the program's
counters ``serving.decode.diffusion.unmasked`` over ``.forwards``.  0.8 where
the rule takes its fallback branch (1 position a denoising forward, 4 of 5
forwards denoise); trained weights past the threshold, or a K/V forward fused
with the next block's first, raise it."""
from chipbench import sdar_decode


def read(observed):
    c = sdar_decode.window_counts(observed)
    if c is None:
        return None
    return c[sdar_decode.PREFIX + "unmasked"] / c[
        sdar_decode.PREFIX + "forwards"]
