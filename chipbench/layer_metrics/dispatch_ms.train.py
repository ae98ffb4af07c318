"""Median host time of one ``exe.run(main, feed, fetch_list)`` call until it
returns its LazyFetch (no readback), over the window; the benchmark's own
``dispatch`` span around the call."""
import statistics


def read(observed):
    spans = observed["spans"].get("dispatch")
    return 1e3 * statistics.median(spans) if spans else None
