"""100 x the HELD experts that took at least one (token, expert) pair in a
decode step / the experts held in its expert layers, over the window (the
counter ``serving.decode.moe.experts_touched{chunk="0"}``): how much of the
held expert weights a step streams."""
from chipbench import trinity_serve as T


def read(observed):
    counts = T.program_counts(observed, 0)
    if counts is None:
        return None
    cfg = observed["config"]
    return 100.0 * counts["experts_touched"] / (
        T.expert_layers(cfg) * cfg["num_experts"])
