"""What the per-layer readers of a LOOPED decode step share (Ouro: one stack
of layers applied ``total_ut_steps`` times a token, a K/V layer a (loop step,
layer)): the step's own counters (``serving.decode.ut.*``; what the decode
program returns behind its tokens), the page walk's device time (the custom
calls under the model's ``ouro.attn`` scope, which sit in the BODY of the
program's loop, so one instruction runs ``total_ut_steps`` times a step; the
driver hands over their names from ``DecodeScheduler.decode_program_text``),
and the bytes a perfect step must move - each a function of
the configuration and the counters alone, independent of how the program
reads the rows or rolls the loop.  Every reader returns None where the
program has no such counter (an older commit, another family), and the run's
line then leaves the metric out.  Times and peaks go through
``kanana_decode`` (``step_device_ms``, ``roofline_pct``: they read no
configuration key)."""
from chipbench import kanana_decode, trace_reduce
from chipbench.glm5_decode import _INSTRUCTION, _item

PREFIX = "serving.decode.ut."
# the model's ``jax.named_scope``s: the loop, and inside its body the
# attention, the feed-forward block and the loop-end norm and gate
LOOP, STAGES = "ouro.loop", {"attn": "ouro.attn", "mlp": "ouro.mlp",
                             "loop_end": "ouro.loop_end"}


def stage_names(program_text):
    """``{"walk": [...], "attn": [...], "mlp": [...], "loop_end": [...]}``:
    the instruction names of a compiled decode program's text by the model's
    scope their ``op_name`` carries INSIDE the loop's scope (the loop's body),
    and among the attention's the custom calls (the page walk).  The profiler
    names a device operation by its instruction, not by the program's scopes,
    so this is how a reader of a device trace finds them."""
    out = {stage: set() for stage in STAGES}
    out["walk"] = set()
    for line in program_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        scopes = m.group(2).split("/")
        if LOOP not in scopes:
            continue
        inside = scopes[scopes.index(LOOP) + 1:]
        for stage, scope in STAGES.items():
            if scope in inside:
                out[stage].add(m.group(1))
                if stage == "attn" and " custom-call(" in line:
                    out["walk"].add(m.group(1))
    return {stage: sorted(names) for stage, names in out.items()}


def step_counts(observed):
    """``{layer_applications, kv_rows_read, served_step_sum}`` of one decode
    step of the window (means over its steps); None where the program counts
    none."""
    c = observed.get("window_counters") or {}
    steps = c.get("serving.decode.steps", 0)
    if not steps or not c.get(PREFIX + "layer_applications"):
        return None
    return {k: c[PREFIX + k] / steps for k in (
        "layer_applications", "kv_rows_read", "served_step_sum")}


def layer_weight_bytes(cfg):
    """Bytes of ONE application of the stack: every layer's four matrices
    (the norms' vectors are 8 KB a layer: counted)."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    HD = cfg["num_attention_heads"] * cfg["head_dim"]
    per = D * 3 * HD + HD * D + D * 2 * F + F * D
    return cfg["num_hidden_layers"] * (
        _item(cfg, "weights_dtype") * per + 4 * 4 * D)


def head_bytes(cfg):
    """The head's matrix, the final norm and the gate; of the embedding only
    the rows looked up."""
    D = cfg["hidden_size"]
    return (_item(cfg, "weights_dtype") * D * (cfg["vocab_size"]
                                               + cfg["slots"])
            + 4 * (2 * D + 1))


def weight_bytes(cfg):
    """Bytes of weights a perfect step reads: the stack ``total_ut_steps``
    times (nothing of 1.23 GB stays on the chip between two loop steps) and
    the head once."""
    return cfg["total_ut_steps"] * layer_weight_bytes(cfg) + head_bytes(cfg)


def reread_bytes(cfg):
    """The part of them that is weights read a SECOND to last time."""
    return (cfg["total_ut_steps"] - 1) * layer_weight_bytes(cfg)


def kv_bytes(cfg, kv_rows_read, layer_applications):
    """Bytes of K and V a step must move: the rows its attention is entitled
    to read (``kv_rows_read`` = sum over slots of ``kv_len``, x the ``U x L``
    K/V layers) and the row each layer application writes, K and V, all
    heads."""
    row = (2 * cfg["num_key_value_heads"] * cfg["head_dim"]
           * _item(cfg, "kv_dtype"))
    return row * (kv_rows_read + layer_applications)


def step_bytes(cfg, counts):
    return weight_bytes(cfg) + kv_bytes(
        cfg, counts["kv_rows_read"], counts["layer_applications"])


def walk_ms(observed):
    """Device milliseconds of one traced decode step inside the page walk:
    the custom calls under ``ouro.attn`` in the loop's body (12 instructions,
    each run ``total_ut_steps`` times a step), by the names the driver read
    out of the compiled program's text."""
    names = (observed.get("decode_stages") or {}).get("walk")
    if not names or "busy_s" not in observed:
        return None
    steps = len(kanana_decode.decode_runs(observed["trace"]))
    if not steps:
        return None
    names = set(names)
    total = trace_reduce.op_time_s(
        observed["trace"], lambda event: event.split(" ", 1)[0] in names)
    return 1e3 * total / steps if total else None
