"""The busiest expert's pairs over the mean expert's, a decode step and layer
(the program's counters ``serving.decode.moe.max_load`` and ``.pairs`` over
the window): 1 is a perfectly even router.  ``expert_load_max_over_mean`` in
this family's key names."""
from chipbench import mellum_decode


def read(observed):
    counts = mellum_decode.step_counts(observed)
    if counts is None or not counts["pairs"]:
        return None
    return counts["max_load"] / (
        counts["pairs"] / observed["config"]["num_experts"])
