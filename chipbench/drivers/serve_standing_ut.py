"""The standing closed loop of ``drivers/serve_standing_moe.py`` (every request
sent through ``InferenceEngine.generate_async`` during SET-UP, the window
opens when each has its first token, nothing arrives in it, all slots decode,
then the requests are cancelled; ``correct`` from the engine's own executables
on its own cache, the kernels stand-alone, and the step functions' replay
against the plain reference) for a LOOPED model: one stack of layers applied
``total_ut_steps`` times a token, a K/V layer a (loop step, layer).  The loop,
clocks, stamps and comparisons are that file's, run from a private copy of the
module whose ``COUNTERS`` are this family's (``serving.decode.ut.*``): the one
thing the loop reads that a model's builder cannot give it.  What differs
between the families is in the builder (``models/ouro.py``): its replay
returns the exit gates of every replayed row in the slot of the loop's routing
comparison (``routing`` in the log is the share of rows whose gates lie within
the builder's ``GATE_TOL``), it reads back the K and V rows of the first and
last layer of EVERY loop step, and it replays twice more as controls (a cache
in 8 bits; a cache that keeps one loop step's rows for all).  After the loop
the compiled decode program's text (kept by the builder while the engine
stood) is reduced to the names of the page walk's instructions, which sit in
the body of the program's loop (``chipbench/ouro_decode.py``).  Every
parameter comes from the configuration's and the mix's files."""
from __future__ import annotations

import importlib.util

from chipbench import ouro_decode
from chipbench.drivers import serve_standing_moe

COUNTERS = ("serving.decode.ut.layer_applications",
            "serving.decode.ut.kv_rows_read",
            "serving.decode.ut.served_step_sum",
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


_LOOP = _loop()


def run(ctx):
    out = _LOOP.run(ctx)
    model = ctx.registry.module("models", ctx.config["model"])
    text = model.LAST.pop("program_text", None)
    out["observed"]["decode_stages"] = (
        ouro_decode.stage_names(text) if text else None)
    return out
