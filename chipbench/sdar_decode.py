"""What the per-layer readers of a BLOCK-DIFFUSION decode step share (SDAR: a
forward carries a block of ``block_length`` positions a slot): the step's own
counters (``serving.decode.diffusion.*`` and ``serving.decode.moe.*``; what the
decode program returns behind its state) and the bytes a perfect forward must
move, each a function of the configuration and the counters alone,
independent of how the program runs the block (how often it writes a block's
rows, whether the K/V forward is fused with the next block's first).  Every
reader returns None where the program has no such counter (an older commit,
another family), and the run's line then leaves the metric out.  Times and
peaks go through ``kanana_decode`` (``kernel_ms``, ``step_device_ms``,
``roofline_pct``: they read no configuration key)."""
PREFIX = "serving.decode.diffusion."
WALK_KERNEL = "paged_gqa_full_attention"


def _item(cfg, key):
    return 2 if cfg[key] == "bfloat16" else 4


def window_counts(observed):
    """The window's totals of the block step's counters; None where the
    program counts none."""
    c = observed.get("window_counters") or {}
    if not c.get(PREFIX + "forwards"):
        return None
    return c


def step_counts(observed):
    """``{forwards, kv_forwards, unmasked, kv_rows_read, experts_touched}`` of
    one decode step of the window (means over its steps: live slots, those
    whose forward wrote K/V, positions unmasked, cached rows read x layers,
    experts that took a pair summed over the layers)."""
    c = window_counts(observed)
    steps = (c or {}).get("serving.decode.steps", 0)
    if not steps:
        return None
    out = {k: c[PREFIX + k] / steps for k in (
        "forwards", "kv_forwards", "unmasked", "kv_rows_read")}
    out["experts_touched"] = c["serving.decode.moe.experts_touched"] / steps
    return out


def dense_bytes(cfg):
    """Bytes of the layers' weights EVERY forward reads whatever it routes:
    each layer's attention matrices, its two norms and two QK norms and its
    router (float32)."""
    D, Dh = cfg["hidden_size"], cfg["head_dim"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = D * (H + 2 * Hkv) * Dh + H * Dh * D
    return cfg["num_hidden_layers"] * (
        _item(cfg, "weights_dtype") * attn
        + 4 * (D * cfg["num_experts"] + 2 * D + 2 * Dh))


def expert_bytes(cfg, experts_touched):
    """Bytes of expert weights a forward reads: the experts that took a pair,
    summed over the layers, x an expert's three matrices."""
    return (_item(cfg, "weights_dtype") * 3 * cfg["hidden_size"]
            * cfg["moe_intermediate_size"] * experts_touched)


def head_bytes(cfg, forwards):
    """The head's matrix and the final norm; of the embedding only the rows
    looked up (``block_length`` a live slot)."""
    D = cfg["hidden_size"]
    return (_item(cfg, "weights_dtype") * D * (
        cfg["vocab_size"] + cfg["block_length"] * forwards) + 4 * D)


def kv_row_bytes(cfg):
    """A token's K and V rows in ONE layer: 2 x 4 x 128 x 2 B = 2048 B."""
    return (2 * cfg["num_key_value_heads"] * cfg["head_dim"]
            * _item(cfg, "kv_dtype"))


def kv_read_bytes(cfg, kv_rows_read):
    """The cached rows a forward's attention is entitled to read
    (``diffusion.kv_rows_read``: sum over live slots of ``kv_len + B``, x the
    layers): the rows as the model defines them, not the whole pages the walk
    copies."""
    return kv_row_bytes(cfg) * kv_rows_read


def kv_write_bytes(cfg, kv_forwards):
    """The rows a perfect program writes: a block's ``B`` rows in every layer
    ONCE, when the block is whole (what the denoising forwards write besides
    is the program's own choice, not work the model asks for)."""
    return (kv_row_bytes(cfg) * cfg["block_length"]
            * cfg["num_hidden_layers"] * kv_forwards)


def step_bytes(cfg, counts):
    """What a perfect forward of the whole step must move."""
    return (dense_bytes(cfg) + expert_bytes(cfg, counts["experts_touched"])
            + head_bytes(cfg, counts["forwards"])
            + kv_read_bytes(cfg, counts["kv_rows_read"])
            + kv_write_bytes(cfg, counts["kv_forwards"]))
