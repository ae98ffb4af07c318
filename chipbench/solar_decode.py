"""What the per-layer readers of a decode step with a gated delta-rule STATE,
one kind of paged grouped-query attention and a SHARE of a routed expert layer
have in common: the step's own counters (``serving.decode.kda.slot_updates``,
``.kv.full_tokens_read``, ``.moe.*`` of the held experts,
``.moe.pairs_elsewhere``; what the decode program returns behind its tokens)
and the name the program gives the state kernel (the device trace names a
custom call after it).  The kernels' times and the rooflines go through
``kanana_decode`` (``kernel_ms``, ``step_device_ms``, ``builder``,
``roofline_pct``: they read no configuration key); the softmax layer's walk is
``mellum_decode.FULL_KERNEL``.  Every reader returns None where the program
has no such kernel or counter (an older commit, another family), and the
run's line then leaves the metric out."""
STATE_KERNEL = "kda_state_decode"
PREFIX = "serving.decode."


def step_counts(observed):
    """``{slot_updates, full_tokens, pairs, experts_touched, max_load,
    pairs_elsewhere}`` of one decode step of the window (means over its
    steps, each summed over the layers); None where the program counts no
    state update."""
    c = observed.get("window_counters") or {}
    steps = c.get(PREFIX + "steps", 0)
    if not steps or not c.get(PREFIX + "kda.slot_updates"):
        return None
    return {"slot_updates": c[PREFIX + "kda.slot_updates"] / steps,
            "full_tokens": c[PREFIX + "kv.full_tokens_read"] / steps,
            "pairs": c[PREFIX + "moe.pairs"] / steps,
            "experts_touched": c[PREFIX + "moe.experts_touched"] / steps,
            "max_load": c[PREFIX + "moe.max_load"] / steps,
            "pairs_elsewhere": c[PREFIX + "moe.pairs_elsewhere"] / steps}
