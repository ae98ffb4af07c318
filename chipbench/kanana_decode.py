"""What the per-layer readers of a latent-cache + routed-experts decode step
share: the two Pallas kernels of a traced ``jit_decode`` run by the ``name=``
the program gives them (the device trace names a custom call after it), the
step's own counters (``serving.decode.moe.*``, ``.latent.tokens_read``; what
the decode program returns behind its tokens) and the configuration's byte
counts (kept in its model builder file, ``models/<config.model>.py``).  Every
reader returns None where the program has no such kernel or counter (an older
commit, another family), and the run's line then leaves the metric out."""
import os

from chipbench import trace_reduce
from chipbench.registry import Registry

MLA_KERNEL = "paged_mla_attention"
MOE_KERNEL = "moe_grouped_matmul"
_REGISTRY = Registry(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def builder(config):
    """The configuration's model builder, from the checkout this file is in."""
    return _REGISTRY.module("models", config["model"])


def decode_runs(trace):
    return trace_reduce.module_runs(
        trace, lambda name: name.startswith("jit_decode"))


def kernel_ms(observed, kernel):
    """Device milliseconds of one traced decode step inside the custom calls
    named ``kernel`` (every layer's summed): the kernels' time in the traced
    window over the ``jit_decode`` runs in it.  The chunk program's calls of
    the same kernels are not in a window in which nothing is prefilled."""
    if "busy_s" not in observed:
        return None
    steps = len(decode_runs(observed["trace"]))
    if not steps:
        return None
    total = trace_reduce.op_time_s(
        observed["trace"],
        lambda name: trace_reduce.op_name(name) == kernel)
    return 1e3 * total / steps if total else None


def step_device_ms(observed):
    """Mean device duration of the ``jit_decode`` program in the trace."""
    if "busy_s" not in observed:
        return None
    runs = decode_runs(observed["trace"])
    return 1e-6 * sum(r[2] for r in runs) / len(runs) if runs else None


def step_counts(observed):
    """``{pairs, experts_touched, max_load, tokens_read}`` of one decode step
    of the window (means over its steps; the first three summed over the
    expert layers, the last over slots and layers); None where the program
    counts none."""
    c = observed.get("window_counters") or {}
    steps = c.get("serving.decode.steps", 0)
    if not steps or not c.get("serving.decode.moe.pairs"):
        return None
    return {"pairs": c["serving.decode.moe.pairs"] / steps,
            "experts_touched": c["serving.decode.moe.experts_touched"] / steps,
            "max_load": c["serving.decode.moe.max_load"] / steps,
            "tokens_read": c["serving.decode.latent.tokens_read"] / steps}


def expert_layers(config):
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def roofline_pct(observed, nbytes, ms):
    if nbytes is None or not ms:
        return None
    return 100.0 * nbytes / observed["peak"]("hbm_bytes_per_s") / (1e-3 * ms)
