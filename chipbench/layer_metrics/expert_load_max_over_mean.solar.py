"""The busiest HELD expert's pairs over the mean held expert's, a decode step
and layer (the program's counters ``serving.decode.moe.max_load`` and
``.pairs`` over the window): 1 is a perfectly even router."""
from chipbench import solar_decode


def read(observed):
    counts = solar_decode.step_counts(observed)
    if counts is None or not counts["pairs"]:
        return None
    return counts["max_load"] / (
        counts["pairs"] / observed["config"]["n_routed_experts"])
