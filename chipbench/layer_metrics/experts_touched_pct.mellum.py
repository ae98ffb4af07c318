"""100 x the experts that took at least one (token, expert) pair in a decode
step / the experts of its layers, over the window (the program's counter
``serving.decode.moe.experts_touched``): how much of the expert weights a
step streams.  ``experts_touched_pct`` in this family's key names."""
from chipbench import mellum_decode


def read(observed):
    counts = mellum_decode.step_counts(observed)
    if counts is None:
        return None
    cfg = observed["config"]
    return 100.0 * counts["experts_touched"] / (
        cfg["num_hidden_layers"] * cfg["num_experts"])
