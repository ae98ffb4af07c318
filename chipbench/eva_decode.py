"""What the per-layer readers of an EVA decode step share: the step's own
counters (``serving.decode.eva.*``: what the decode program returns behind its
bytes), the name the program gives its attention kernel (the device trace
names a custom call after it), and the rows and bytes a perfect step must
move.  The kernel's time and the step's go through ``kanana_decode``
(``kernel_ms``, ``step_device_ms``, ``roofline_pct``: they read no
configuration key).  Every reader returns None where the program has no such
kernel or counter (an older commit, another family), and the run's line then
leaves the metric out."""
KERNEL = "eva_window_summary_decode"
_EVA = "serving.decode.eva."


def step_counts(observed):
    """``{window_rows, summary_rows, chunks, windows}`` of one decode step of
    the window (means over its steps; rows summed over the slots, a layer);
    None where the program counts none."""
    c = observed.get("window_counters") or {}
    steps = c.get("serving.decode.steps", 0)
    if not steps or not c.get(_EVA + "window_rows_read"):
        return None
    return {"window_rows": c[_EVA + "window_rows_read"] / steps,
            "summary_rows": c[_EVA + "summary_rows_read"] / steps,
            "chunks": c[_EVA + "chunks_summarised"] / steps,
            "windows": c[_EVA + "windows_closed"] / steps}


def _item(cfg, key):
    return 2 if cfg[key] == "bfloat16" else 4


def row_bytes(cfg):
    """Bytes of one K and one V row (or one summary pair) of ONE layer: every
    head has its own, ``hidden_size`` values each."""
    return 2 * cfg["hidden_size"] * _item(cfg, "kv_dtype")


def attention_bytes(cfg, counts):
    """Bytes a step's attention must read: the window rows and the visible
    summary rows of every slot, in every layer.  The rows as the model
    defines them, not the whole pages the walk copies."""
    return (counts["window_rows"] + counts["summary_rows"]) * row_bytes(
        cfg) * cfg["num_hidden_layers"]


def written_bytes(cfg, counts):
    """Bytes a step writes to the cache and reads back to pool: a K and V row
    a live slot (one window closes every ``window_size`` steps of a slot, so
    live slots = chunks x chunk_size), a summary pair a completed chunk and
    the chunk's rows read once more, in every layer."""
    C = cfg["chunk_size"]
    return (counts["chunks"] * (C + 1 + C)) * row_bytes(cfg) * cfg[
        "num_hidden_layers"]


def weight_bytes(cfg):
    """Bytes of weights EVERY decode step reads: each layer's four attention
    matrices and three feed-forward ones, the head (float32); of the
    embedding only the rows looked up."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    layer = 4 * D * D + 3 * D * F
    return (_item(cfg, "weights_dtype") * (
        cfg["num_hidden_layers"] * layer + cfg["slots"] * D)
        + 4 * D * cfg["num_pred_heads"] * cfg["vocab_size"])
