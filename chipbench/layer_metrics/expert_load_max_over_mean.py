"""The busiest expert's pairs over the mean expert's, a decode step and expert
layer (the program's counters ``serving.decode.moe.max_load`` and ``.pairs``
over the window): 1 is a perfectly even router; the grouped product's longest
group is this many times the mean."""
from chipbench import kanana_decode


def read(observed):
    counts = kanana_decode.step_counts(observed)
    if counts is None:
        return None
    return counts["max_load"] / (
        counts["pairs"] / observed["config"]["n_routed_experts"])
