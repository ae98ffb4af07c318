"""95th percentile of the program's histogram ``serving.decode.queue_wait``
(admission to the first prefill dispatch) over the window."""


def read(observed):
    h = observed["histograms"]["serving.decode.queue_wait"]
    return 1e3 * h.quantile(0.95) if h.count else None
