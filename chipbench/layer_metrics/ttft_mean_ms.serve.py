"""Mean, over the window's requests, of the time from the instant a request
was DUE to its first token; see ``ttft_p95_ms.serve``.  The steadiest of the
three across seeds (8.5% against 13% for the median and 23% for the 95th
percentile, PERF.md section 6): the figure a PR that claims ``itl_p95_ms``
shows to be no worse."""


def read(observed):
    return observed.get("ttft_mean_ms")
