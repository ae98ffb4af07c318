"""The standing closed loop of ``drivers/serve_standing_moe.py`` (every request
sent through ``InferenceEngine.generate_async`` during SET-UP, the window
opens when each has its first byte, nothing arrives in it, all slots decode,
then the requests are cancelled; ``correct`` from the engine's own executables
on its own cache, the mechanisms stand-alone, and the step functions' replay
against the plain reference) for a model whose K and V live in a further page
group under an ALIGNED window and whose first group holds summary rows on
pages of their own size.  The loop, clocks, stamps and comparisons are that
file's, run from a private copy of the module whose ``COUNTERS`` are this
family's (``serving.decode.eva.*``, ``serving.cache.window.pages_released``):
the one thing the loop reads that a model's builder cannot give it.  What
differs between the families is in the builder (``models/evabyte.py``): its
replay ends ACROSS a multiple of the window (the last chunks of the closing
window summarised by decode steps, the window given back whole, the next begun
from one row), it compares the logits of all 8 prediction heads, reads back
the window's K and V rows and the summary rows of every layer, and holds the
drain to both groups' pages; the loop's routed-expert comparisons read sets of
one column that always agree.  Every parameter comes from the configuration's
and the mix's files."""
from __future__ import annotations

import importlib.util

from chipbench.drivers import serve_standing_moe

COUNTERS = ("serving.decode.eva.window_rows_read",
            "serving.decode.eva.summary_rows_read",
            "serving.decode.eva.chunks_summarised",
            "serving.decode.eva.windows_closed",
            "serving.cache.window.pages_released",
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


def _stalls():
    """The loop's own account of its stalls over the process, from the
    program's cells (``docs/observability.md``, "Debugging a stalled
    replica"): how many commit-to-commit intervals stood above three times
    their kind's baseline, their excess in all and at the longest, the excess
    by the phase each was put down to, and the collector's seconds.  None
    where the program keeps no such cell."""
    from paddle_tpu import observability as obs

    tel = obs.get_telemetry()
    cell = tel.histograms().get("serving.decode.stall")
    if cell is None:
        return None
    snap = cell.snapshot()
    where = {name.split("where=", 1)[1].strip('"}'): round(c.value, 4)
             for name, c in tel.counters().items()
             if name.startswith("serving.decode.stall_seconds")}
    gc_s = sum(h.snapshot().sum for name, h in tel.histograms().items()
               if name.startswith("host.gc"))
    return {"stalls": snap.count, "excess_s": round(snap.sum, 4),
            "longest_s": round(snap.max or 0.0, 4), "by_phase_s": where,
            "host_gc_s": round(gc_s, 4)}


def run(ctx):
    """The loop, and one line more in the log: an untraced run's stalls (a
    traced run's are its tracer thread's own, so no per-layer metric reads
    them; ``loop_cells.py``)."""
    out = _LOOP.run(ctx)
    ctx.log("standing: the loop's stalls over the process: %s" % _stalls())
    return out
