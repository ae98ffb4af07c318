"""What an always-on path costs, as a count: the function calls it makes.

A budget in microseconds on a shared CPU fails beside six busy workers and
passes a path that doubled on an idle one; the calls a path makes are the
same on every machine."""
import sys

__all__ = ["calls_per"]


def calls_per(fn, n=1000):
    """Python and C function calls one ``fn()`` makes on this thread (``fn``'s
    own frame included), averaged over ``n`` calls."""
    fn()                           # first-use imports are not the path's
    calls = [0]

    def count(frame, event, arg):
        if event in ("call", "c_call"):
            calls[0] += 1

    old = sys.getprofile()
    sys.setprofile(count)
    try:
        for _ in range(n):
            fn()
    finally:
        sys.setprofile(old)
    return (calls[0] - 1) / float(n)   # less the closing setprofile
