"""What the per-layer readers of a DeepSeek-sparse-attention decode step share
(GLM-5: an indexer with a key cache of its own, an exact selection, latent
attention over a row list, a share of a routed expert layer): the step's own
counters (``serving.decode.sparse.*``, ``.index.rows_scored``, ``.moe.*``;
what the decode program returns behind its tokens), the device time of each
of the three STAGES of the sparse attention, and the bytes a perfect step must
move — each a function of the configuration and the counters alone,
independent of how the program reads the rows.

A stage is a ``jax.named_scope`` of the model (``dsa_index``, ``dsa_select``,
``mla_rows``).  The profiler names a device operation by its instruction, not
by the program's scopes, so the driver hands over the compiled decode
program's text (``DecodeScheduler.decode_program_text``): an instruction
belongs to the stage whose scope its ``op_name`` metadata carries — a Pallas
kernel and the gathers, loops and fusions XLA makes of the rest alike.  Every
reader returns None where the program has no such counter, scope or text (an
older commit, another family), and the run's line then leaves the metric out.
The kernels' times and the rooflines go through ``kanana_decode``."""
import re

from chipbench import kanana_decode, trace_reduce

STAGES = {"index": "dsa_index", "select": "dsa_select", "rows": "mla_rows"}
PREFIX = "serving.decode."
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*op_name=\"([^\"]*)\"")


def stage_names(program_text):
    """``{stage: [instruction names]}`` of a compiled program's text."""
    out = {stage: set() for stage in STAGES}
    for line in program_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        scopes = m.group(2).split("/")
        for stage, scope in STAGES.items():
            if scope in scopes:
                out[stage].add(m.group(1))
    return {stage: sorted(names) for stage, names in out.items()}


def stage_ms(observed, stage):
    """Device milliseconds of one traced decode step inside ``stage``'s
    instructions (self times, every layer's summed)."""
    names = (observed.get("decode_stages") or {}).get(stage)
    if not names or "busy_s" not in observed:
        return None
    steps = len(kanana_decode.decode_runs(observed["trace"]))
    if not steps:
        return None
    names = set(names)
    total = trace_reduce.op_time_s(
        observed["trace"], lambda event: event.split(" ", 1)[0] in names)
    return 1e3 * total / steps if total else None


def step_counts(observed):
    """``{selected, visible, rows_scored, pairs, experts_touched,
    pairs_elsewhere}`` of one decode step of the window (means over its
    steps, each summed over slots and layers); None where the program scores
    no indexer key."""
    c = observed.get("window_counters") or {}
    steps = c.get(PREFIX + "steps", 0)
    if not steps or not c.get(PREFIX + "index.rows_scored"):
        return None
    return {"selected": c[PREFIX + "sparse.selected_tokens"] / steps,
            "visible": c[PREFIX + "sparse.visible_tokens"] / steps,
            "rows_scored": c[PREFIX + "index.rows_scored"] / steps,
            "pairs": c[PREFIX + "moe.pairs"] / steps,
            "experts_touched": c[PREFIX + "moe.experts_touched"] / steps,
            "pairs_elsewhere": c[PREFIX + "moe.pairs_elsewhere"] / steps}


def _item(cfg, key):
    return 2 if cfg[key] == "bfloat16" else 4


def index_bytes(cfg, rows_scored):
    """Bytes the indexer must read: one key of ``index_head_dim`` values for
    every visible token of every slot in every layer."""
    return _item(cfg, "kv_dtype") * cfg["index_head_dim"] * rows_scored


def rows_bytes(cfg, selected):
    """Bytes the attention must read: ``[c | k_pe]`` of every SELECTED token,
    once, whatever gathers them (the row as the model defines it, 512 + 64,
    not the 640 lanes the pool pads it to)."""
    return _item(cfg, "kv_dtype") * (
        cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * selected


def expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_bytes(cfg, experts_touched):
    """Bytes of routed-expert weights a step reads: the HELD experts that
    took a pair, summed over the expert layers."""
    return _item(cfg, "weights_dtype") * expert_params(cfg) * experts_touched


def weight_bytes(cfg):
    """Bytes of weights EVERY decode step reads whatever it routes or
    selects: each layer's attention and indexer matrices, the dense block,
    the shared experts, the routers over all experts (float32) and the head;
    of the embedding only the rows looked up."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    R, Rq = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    Hi, Di = cfg["index_n_heads"], cfg["index_head_dim"]
    L = cfg["num_hidden_layers"]
    n_dense = min(cfg["first_k_dense_replace"], L)
    n_moe = L - n_dense
    attn = (D * (Rq + R + dr + Di + Hi) + Rq * (H * (dn + dr) + Hi * Di)
            + H * (dn + dv) * R + H * dv * D)
    n = (L * attn + n_dense * 3 * D * cfg["intermediate_size"]
         + n_moe * cfg["n_shared_experts"] * expert_params(cfg)
         + D * cfg["vocab_size"] + cfg["slots"] * D)
    return (_item(cfg, "weights_dtype") * n
            + 4 * n_moe * (D + 1) * cfg["router_experts"])


def written_bytes(cfg):
    """Bytes a step writes to the cache: a latent row and an indexer key a
    slot a layer."""
    return (_item(cfg, "kv_dtype") * cfg["slots"] * cfg["num_hidden_layers"]
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
               + cfg["index_head_dim"]))


def step_bytes(cfg, counts):
    """What a perfect decode step must move, the whole of it."""
    return (weight_bytes(cfg) + expert_bytes(cfg, counts["experts_touched"])
            + index_bytes(cfg, counts["rows_scored"])
            + rows_bytes(cfg, counts["selected"]) + written_bytes(cfg))
