"""100 x the experts that took at least one (row, expert) pair in a forward /
the experts of its layers, over the window (the program's counter
``serving.decode.moe.experts_touched``): how much of the expert weights a
forward streams.  ``experts_touched_pct`` for a block step."""
from chipbench import sdar_decode


def read(observed):
    counts = sdar_decode.step_counts(observed)
    if counts is None:
        return None
    cfg = observed["config"]
    return 100.0 * counts["experts_touched"] / (
        cfg["num_hidden_layers"] * cfg["num_experts"])
