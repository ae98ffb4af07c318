"""The loop step a live slot is served from, 1-based, mean over the window's
steps and slots: the program's counter ``serving.decode.ut.served_step_sum``
over the live slots (``ut.layer_applications`` / (``total_ut_steps x
num_hidden_layers``)).  ``total_ut_steps`` at the published
``early_exit_threshold`` 1; an exit rule acted on would show here."""
from chipbench import ouro_decode


def read(observed):
    counts = ouro_decode.step_counts(observed)
    if counts is None:
        return None
    cfg = observed["config"]
    live = counts["layer_applications"] / (
        cfg["total_ut_steps"] * cfg["num_hidden_layers"])
    return counts["served_step_sum"] / live if live else None
