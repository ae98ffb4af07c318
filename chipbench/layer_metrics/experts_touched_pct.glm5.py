"""100 x the HELD experts that took at least one (token, expert) pair in a
decode step / the experts held in its expert layers, over the window (the
program's counter ``serving.decode.moe.experts_touched``): how much of the
held expert weights a step streams."""
from chipbench import glm5_decode


def read(observed):
    counts = glm5_decode.step_counts(observed)
    if counts is None:
        return None
    cfg = observed["config"]
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return 100.0 * counts["experts_touched"] / (
        layers * cfg["n_routed_experts"])
