"""What the per-layer readers of ``trinity_open_mixedlen`` share: the bytes and
operations a perfect decode step and a perfect prefill chunk of the AFMoE
family must move (functions of the configuration's keys and of the programs'
own counters), the counters themselves (``serving.decode.<name>{chunk="0"}``
the decode steps', ``{chunk="1"}`` the chunk programs': what each program
returns behind its tokens, taken at the window's edges and at the trace's by
``drivers/serve_open_moe.py``), and the device time of a kernel INSIDE the
programs of one kind: in an open loop chunks and steps alternate in the traced
window and call the same kernels, so a kernel's time is put down to the
``jit_decode`` or the ``jit_chunk`` run it started in.  Every reader returns
None where the program has no such counter or the trace no such program (an
older commit, another family, a CPU rehearsal), and the run's line then leaves
the metric out.

Counted is what a program really reads: the weights every program reads
whatever it routes, the held experts that TOOK a pair (an untouched expert is
not read), the K and V rows each kind of layer is entitled to (a window row
once a step), the rows it writes."""
import bisect

from chipbench import loop_cells, mellum_decode, trace_reduce

PREFIX = "serving.decode."
MOE_KERNEL = "moe_grouped_matmul"
WALK_KERNELS = (mellum_decode.FULL_KERNEL, mellum_decode.WINDOW_KERNEL)
TRACED = "traced_counters"      # the counters between the trace's edges
PROGRAMS = {0: ("jit_decode", PREFIX + "steps"),
            1: ("jit_chunk", PREFIX + "prefills")}
NAMES = {"pairs": "moe.pairs", "pairs_held": "moe.pairs_held",
         "experts_touched": "moe.experts_touched",
         "full_tokens": "kv.full_tokens_read",
         "window_tokens": "kv.window_tokens_read"}


# -- the counters --------------------------------------------------------------

def program_counts(observed, chunk, table="window_counters"):
    """Means over the programs of one kind (``chunk`` 0: decode steps, 1:
    prefill chunks) that committed between the window's edges, or between the
    trace's (``table="traced_counters"``: what a reader that divides by the
    TRACED programs' time takes, the seated slots being what they were
    then), of the family's step counters, each summed over the layers as the
    program sums it, and ``rows``: the real rows a program computed (live
    slots, a chunk's valid tokens); None where the program counts none."""
    c = observed.get(table) or {}
    runs = c.get(PROGRAMS[chunk][1], 0)
    cells = {k: loop_cells.labeled(PREFIX + n, chunk=chunk)
             for k, n in NAMES.items()}
    if not runs or any(cell not in c for cell in cells.values()):
        return None
    out = {k: c[cell] / runs for k, cell in cells.items()}
    cfg = observed["config"]
    out["rows"] = out["pairs"] / (cfg["num_experts_per_tok"]
                                  * expert_layers(cfg))
    return out


# -- sizes ---------------------------------------------------------------------

def _item(cfg, key="weights_dtype"):
    return 2 if cfg[key] == "bfloat16" else 4


def expert_layers(cfg):
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def attention_params(cfg):
    """One layer's q, k, v, gate and output matrices."""
    n_q = cfg["num_attention_heads"] * cfg["head_dim"]
    n_kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return cfg["hidden_size"] * (2 * n_q + 2 * n_kv) + n_q * cfg["hidden_size"]


def expert_params(cfg):
    """Parameters of ONE routed expert (gate, up, down)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def resident_params(cfg):
    """Matrix parameters every row passes whatever it routes: attention in
    every layer, the dense feed-forward blocks, the shared experts."""
    dense = 3 * cfg["hidden_size"] * cfg["intermediate_size"]
    shared = cfg["num_shared_experts"] * expert_params(cfg)
    return (cfg["num_hidden_layers"] * attention_params(cfg)
            + cfg["num_dense_layers"] * dense + expert_layers(cfg) * shared)


def router_params(cfg):
    return expert_layers(cfg) * cfg["hidden_size"] * cfg["router_experts"]


def kv_row_bytes(cfg):
    """A K and a V row of one token in one layer."""
    return (2 * cfg["num_key_value_heads"] * cfg["head_dim"]
            * _item(cfg, "kv_dtype"))


def weight_bytes(cfg, rows, head_rows):
    """Bytes of weights a program of ``rows`` rows reads whatever it routes:
    the resident matrices, the routers (float32), the norms' vectors, the
    embedding rows looked up, and the head where ``head_rows`` rows need
    logits (every slot of a decode step, the last row of a chunk)."""
    D, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    vectors = 4 * (L * (4 * D + 2 * cfg["head_dim"]) + D)
    head = D * cfg["vocab_size"] if head_rows else 0
    return (_item(cfg) * (resident_params(cfg) + head + rows * D)
            + 4 * router_params(cfg) + vectors)


def expert_bytes(cfg, experts_touched):
    """Bytes of routed-expert weights a program reads: the held experts that
    took a pair, summed over the layers (the counter ``moe.experts_touched``)."""
    return _item(cfg) * expert_params(cfg) * experts_touched


def kv_bytes(cfg, counts):
    """Bytes of K and V rows a program's attention reads (the positions each
    kind of layer is entitled to, already summed over the kind's layers) and
    writes (a row a token a layer)."""
    return kv_row_bytes(cfg) * (counts["full_tokens"] + counts["window_tokens"]
                                + counts["rows"] * cfg["num_hidden_layers"])


def program_bytes(cfg, counts, head_rows):
    return (weight_bytes(cfg, counts["rows"], head_rows)
            + expert_bytes(cfg, counts["experts_touched"])
            + kv_bytes(cfg, counts))


def program_flops(cfg, counts, head_rows):
    """Operations of one program: every real row through the resident
    matrices and the routers, ``head_rows`` rows through the head, a held
    pair through one expert, and per key read a dot product and a weighted
    sum over every query head: exact for a decode step (one query row a
    slot), a FLOOR for a chunk, whose rows share the keys the counters count
    once (a chunk is bound by its bytes: the floor keeps its share honest)."""
    dense = 2 * counts["rows"] * (resident_params(cfg) + router_params(cfg))
    head = 2 * head_rows * cfg["hidden_size"] * cfg["vocab_size"]
    experts = 2 * expert_params(cfg) * counts["pairs_held"]
    attn = (4 * cfg["num_attention_heads"] * cfg["head_dim"]
            * (counts["full_tokens"] + counts["window_tokens"]))
    return dense + head + experts + attn


# -- the trace -----------------------------------------------------------------

def runs(observed, chunk):
    """``[name, start_ns, duration_ns]`` of the traced programs of one kind;
    None where there is no device trace."""
    if "busy_s" not in observed:
        return None
    prefix = PROGRAMS[chunk][0]
    return trace_reduce.module_runs(
        observed["trace"], lambda name: name.startswith(prefix)) or None


def program_ms(observed, chunk):
    found = runs(observed, chunk)
    return None if found is None else 1e-6 * sum(
        r[2] for r in found) / len(found)


def kernel_ms(observed, chunk, kernels):
    """Device milliseconds one traced program of the kind spends inside the
    custom calls named ``kernels`` (every layer's summed): the time of those
    calls that START inside a run of the kind, over its runs.  A custom call
    encloses nothing, so its duration is its own time."""
    found = runs(observed, chunk)
    if found is None:
        return None
    trace = observed["trace"]
    spans = sorted((s, s + d) for _, s, d in found)
    starts = [s for s, _ in spans]
    total = 0
    for name, s, d in trace_reduce.line_events(
            trace, trace_reduce.device_planes(trace)[0],
            trace_reduce.OPS_LINE):
        if trace_reduce.op_name(name) in kernels:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < spans[i][1]:
                total += d
    return 1e-6 * total / len(found) if total else None


def share_pct(seconds_at_peak, ms):
    if seconds_at_peak is None or not ms:
        return None
    return 100.0 * seconds_at_peak / (1e-3 * ms)


def hbm_s(observed, nbytes):
    return nbytes / observed["peak"]("hbm_bytes_per_s")
