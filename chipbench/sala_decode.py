"""What the per-layer readers of a block-sparse + linear-state decode step
share: which device operations of a traced decode step belong to the sparse
layers' selection and attention and which to the linear-attention state, the
step's own token counts, and the configuration's byte counts (kept in its
model builder file, ``models/<config.model>.py``).

The profiler names a device operation by its instruction and result shape, not
by the program's scopes, so the operations are told apart by what only they
carry: the selection works on arrays with a dimension of blocks, half-kernels
or pooled kernels of the whole page-table span (``MP``, ``MP * B/s``, that
less ``l/s - 1``; the gather of the pooled keys by page table has every
slot's pages, ``slots * MP``), the paged attention is the Pallas kernel ``paged_gqa_decode_attention``, and
the state update writes ``[.., H, d, d]``.  Each time is therefore a LOWER
BOUND of its layer's: the read ``q . S`` of the state has a result without
those dimensions where the compiler does not fuse it into the update, and
the ``[slots, Hkv, listed]`` page-list operations carry no selection
dimension; both are left out, and a roofline share over such a time reads
high by as much.  The configuration commits how many instructions the shapes
take (``decode_step_ops``): see :func:`per_step_ms`."""
import os
import re

from chipbench import trace_reduce
from chipbench.registry import Registry

# the Pallas kernel of the selected-page attention, by the ``name=`` the
# program gives it (the device trace names the custom call after it)
DECODE_KERNEL = "paged_gqa_decode_attention"
_REGISTRY = Registry(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def builder(config):
    """The configuration's model builder, from the checkout this file is in."""
    return _REGISTRY.module("models", config["model"])


def _dims(event_name):
    shape = event_name.rsplit(" ", 1)[-1]
    return [int(x) for x in re.findall(r"\d+", shape.split("[", 1)[-1])] \
        if "[" in shape else []


def matchers(config):
    """``(is_sparse, is_state)``: predicates over a device event's name
    (``name opcode shape``)."""
    sp = config["sparse_config"]
    mp = -(-config["max_seq_len"] // config["page"])
    per = sp["block_size"] // sp["kernel_stride"]
    span = sp["kernel_size"] // sp["kernel_stride"]
    marks = {mp, mp * per, mp * per - span + 1, config["slots"] * mp}
    tail = [config["lightning_nh"], config["lightning_head_dim"],
            config["lightning_head_dim"]]

    def is_state(name):
        return _dims(name)[-3:] == tail

    def is_sparse(name):
        if trace_reduce.op_name(name) == DECODE_KERNEL:
            return True
        return not is_state(name) and bool(marks.intersection(_dims(name)))

    return is_sparse, is_state


def decode_runs(trace):
    return trace_reduce.module_runs(
        trace, lambda name: name.startswith("jit_decode"))


def matched_ops(observed, which):
    """Names of the distinct device instructions of the traced decode steps
    that ``which`` (``"sparse"`` / ``"state"``) takes, sorted."""
    is_sparse, is_state = matchers(observed["config"])
    match = is_sparse if which == "sparse" else is_state
    return sorted({e[0] for p in trace_reduce.device_planes(observed["trace"])
                   for e in trace_reduce.line_events(
                       observed["trace"], p, trace_reduce.OPS_LINE)
                   if match(e[0])})


def per_step_ms(observed, which):
    """Device milliseconds of one traced decode step in the ``which``
    (``"sparse"`` / ``"state"``) operations; None without a device trace.

    A LOWER BOUND of the layer's time (the module docstring says what a
    shape cannot catch), and a tripwire: the configuration's file commits
    how many distinct instructions of the decode program the shapes take
    (``decode_step_ops``).  Where a traced program matches another number
    (a later PR fused, split or reshaped an operation of the layer) the
    reader returns None and the run's line lacks the metric, so the change
    is met by whoever made it and not read as the layer moving."""
    if "busy_s" not in observed or "sparse_config" not in observed["config"]:
        return None
    steps = len(decode_runs(observed["trace"]))
    if not steps:
        return None
    ops = matched_ops(observed, which)
    if len(ops) != observed["config"]["decode_step_ops"][which]:
        return None
    names = set(ops)
    return 1e3 * trace_reduce.op_time_s(
        observed["trace"], lambda name: name in names) / steps


def step_device_ms(observed):
    """Mean device duration of the ``jit_decode`` program in the trace."""
    if "busy_s" not in observed:
        return None
    runs = decode_runs(observed["trace"])
    return 1e-6 * sum(r[2] for r in runs) / len(runs) if runs else None


def step_tokens(observed):
    """``(selected, visible)`` tokens of one decode step of the window, summed
    over slots, sparse layers and KV heads; None where the program counts
    none (an older commit)."""
    c = observed.get("window_counters") or {}
    steps = c.get("serving.decode.steps", 0)
    visible = c.get("serving.decode.sparse.visible_tokens", 0)
    if not steps or not visible:
        return None
    return (c["serving.decode.sparse.selected_tokens"] / steps,
            visible / steps)


def roofline_pct(observed, nbytes, ms):
    if nbytes is None or not ms:
        return None
    return 100.0 * nbytes / observed["peak"]("hbm_bytes_per_s") / (1e-3 * ms)
