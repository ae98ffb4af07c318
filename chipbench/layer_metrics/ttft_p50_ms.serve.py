"""Median, over the window's requests, of the time from the instant a request
was DUE to its first token; see ``ttft_p95_ms.serve``."""


def read(observed):
    return observed.get("ttft_p50_ms")
