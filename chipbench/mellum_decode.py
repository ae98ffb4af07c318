"""What the per-layer readers of a decode step with TWO kinds of paged
attention (full and sliding-window layers in page groups of their own) and
routed experts share: the step's own counters (``serving.decode.moe.*``,
``.kv.full_tokens_read``, ``.kv.window_tokens_read``; what the decode program
returns behind its tokens) and the names the program gives the two attention
kernels (the device trace names a custom call after it).  The kernels' times
and the byte counts go through ``kanana_decode`` (``kernel_ms``,
``step_device_ms``, ``builder``, ``roofline_pct``: they read no configuration
key).  Every reader returns None where the program has no such kernel or
counter (an older commit, another family), and the run's line then leaves the
metric out."""
FULL_KERNEL = "paged_gqa_full_attention"
WINDOW_KERNEL = "paged_gqa_window_attention"


def step_counts(observed):
    """``{pairs, experts_touched, max_load, full_tokens, window_tokens}`` of
    one decode step of the window (means over its steps; the first three
    summed over the layers, the last two over slots and the kind's layers);
    None where the program counts none."""
    c = observed.get("window_counters") or {}
    steps = c.get("serving.decode.steps", 0)
    if not steps or not c.get("serving.decode.kv.full_tokens_read"):
        return None
    return {"pairs": c["serving.decode.moe.pairs"] / steps,
            "experts_touched": c["serving.decode.moe.experts_touched"] / steps,
            "max_load": c["serving.decode.moe.max_load"] / steps,
            "full_tokens": c["serving.decode.kv.full_tokens_read"] / steps,
            "window_tokens": c["serving.decode.kv.window_tokens_read"] / steps}


def layers_of(config, kind):
    return list(config["layer_types"]).count(kind)
