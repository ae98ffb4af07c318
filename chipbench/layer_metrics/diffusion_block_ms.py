"""Mean of the program's span ``serving.decode.block`` over the window: from
the dispatch of a block's first forward to the readback of the forward that
wrote its K/V, a slot: the gap between a client's bursts of tokens."""


def read(observed):
    h = (observed.get("histograms") or {}).get("serving.decode.block")
    return 1e3 * h.sum / h.count if h is not None and h.count else None
