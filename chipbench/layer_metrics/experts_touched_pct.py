"""100 x the experts that took at least one (token, expert) pair in a decode
step / the experts of its expert layers, over the window (the program's counter
``serving.decode.moe.experts_touched``): how much of the expert weights a
step streams."""
from chipbench import kanana_decode


def read(observed):
    counts = kanana_decode.step_counts(observed)
    if counts is None:
        return None
    cfg = observed["config"]
    return 100.0 * counts["experts_touched"] / (
        kanana_decode.expert_layers(cfg) * cfg["n_routed_experts"])
