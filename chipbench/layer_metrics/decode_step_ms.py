"""Median of the program's histogram ``serving.decode.step`` over the window:
one scheduler iteration on the host clock, readback included."""


def read(observed):
    h = observed["histograms"]["serving.decode.step"]
    return 1e3 * h.quantile(0.5) if h.count else None
