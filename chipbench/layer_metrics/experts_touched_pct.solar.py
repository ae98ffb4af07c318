"""100 x the HELD experts that took at least one (token, expert) pair in a
decode step / the experts held in its layers, over the window (the program's
counter ``serving.decode.moe.experts_touched``): how much of the held expert
weights a step streams."""
from chipbench import solar_decode


def read(observed):
    counts = solar_decode.step_counts(observed)
    if counts is None:
        return None
    cfg = observed["config"]
    return 100.0 * counts["experts_touched"] / (
        cfg["num_hidden_layers"] * cfg["n_routed_experts"])
