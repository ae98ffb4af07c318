"""The standing closed loop of ``drivers/serve_standing_moe.py`` (every request
sent through ``InferenceEngine.generate_async`` during SET-UP, the window
opens when each has its first token, nothing arrives in it, all slots decode,
then the requests are cancelled; ``correct`` from the engine's own executables
on its own cache, the mechanisms stand-alone, and the step functions' replay
against the plain reference over the same experts) for a model that keeps a
gated delta-rule STATE and a convolution's last inputs a slot beside paged
K/V, and whose feed-forward blocks are a SHARE of a routed expert layer.  The
loop, clocks, stamps and comparisons are that file's, run from a private copy
of the module whose ``COUNTERS`` are this family's (``serving.decode.kda.*``,
``.kv.full_tokens_read``, ``.moe.*`` of the HELD experts and
``.moe.pairs_elsewhere``): the one thing the loop reads that a model's builder
cannot give it.  What differs between the families is in the builder
(``models/<config.model>.py``): here ``served_state_errors`` prefills the
longest checked request again through the engine's programs into a slot the
engine has used, reads the K and V rows and BOTH slot-state leaves they leave,
and ``deep_row_errors`` holds those leaves to the reference's token-by-token
state.  Every parameter comes from the configuration's and the mix's files."""
from __future__ import annotations

import importlib.util

from chipbench.drivers import serve_standing_moe

COUNTERS = ("serving.decode.kda.slot_updates",
            "serving.decode.kv.full_tokens_read",
            "serving.decode.moe.pairs", "serving.decode.moe.experts_touched",
            "serving.decode.moe.max_load",
            "serving.decode.moe.pairs_elsewhere",
            "serving.decode.prefill_tokens", "serving.decode.steps")


def _loop():
    """``serve_standing_moe`` once more, as a module of its own, reading
    ``COUNTERS`` above (the accepted file and its module are left alone)."""
    spec = importlib.util.spec_from_file_location(
        __name__ + "_loop", serve_standing_moe.__file__)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.COUNTERS = COUNTERS
    return module


run = _loop().run
