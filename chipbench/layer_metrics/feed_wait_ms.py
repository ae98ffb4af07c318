"""Median time ``next()`` on the prefetched feed blocked before a step (the
benchmark's ``feed`` span)."""
import statistics


def read(observed):
    spans = observed["spans"].get("feed")
    return 1e3 * statistics.median(spans) if spans else None
