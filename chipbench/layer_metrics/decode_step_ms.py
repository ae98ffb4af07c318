"""Median of the program's histogram ``serving.decode.step`` over the window,
on the host clock.  Since PR 36 one step stays in flight, and the span runs
from the DISPATCH of step n+1 to the READBACK of step n: the iteration less
its plan, commit, admit and sweep, which run under the device's step, and not
one step's dispatch plus its wait."""


def read(observed):
    h = observed["histograms"]["serving.decode.step"]
    return 1e3 * h.quantile(0.5) if h.count else None
