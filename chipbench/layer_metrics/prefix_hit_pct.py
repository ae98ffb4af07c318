"""KV pages served from the prefix cache over prompt pages looked up, over the
window (``engine.health()["decode"]["prefix"]``: hit / (hit + miss))."""


def read(observed):
    p = observed["prefix"]
    total = p["kv_hit_pages"] + p["kv_miss_pages"]
    return 100.0 * p["kv_hit_pages"] / total if total else None
