"""95th percentile, over the window's requests, of the time from the instant
a request was DUE to its first token (``token_times[0]``; open loop, so a
stall counts against the requests behind it; a failed or short request counts
as the window's length).  Recorded, not judged: with some 190 requests in a
window and scheduler iterations of 37-84 ms it spreads by 4-9% between runs
of one schedule and by 23% across seeds, each of which orders the schedule
its own way (PERF.md section 6): wider than any bound the contract admits."""


def read(observed):
    return observed.get("ttft_p95_ms")
