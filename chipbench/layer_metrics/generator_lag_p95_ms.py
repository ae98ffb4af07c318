"""How late the load generator sent against its schedule (95th percentile
over the window's requests): a starved generator must not read as a fast
server."""


def read(observed):
    return observed.get("generator_lag_p95_ms")
