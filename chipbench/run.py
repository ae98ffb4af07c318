"""Run one cell of BENCHMARK.json on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: loads the cell's configuration and traffic mix by name, builds
the system through the program's normal entry points, warms up the cell's own
shapes (set-up), measures for ``--seconds``, checks the outputs, and prints one
JSON object as the last line of stdout.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics (a profiler trace of a
part of the window is reduced by ``trace_reduce.py``).  It refuses to run
where jax has no TPU or not the number of chips the cell asks for: there is no
CPU fallback (tests call :func:`run_cell` with the place passed in).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import peaks, profiling, trace_reduce  # noqa: E402
from chipbench.registry import Registry  # noqa: E402

COMPILE_EVENTS = ("/jax/compilation_cache/cache_hits",
                  "/jax/compilation_cache/cache_misses")


def process_age_s():
    """Seconds since this process started (interpreter start-up included)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


class CompileCounter:
    """jax's own compile requests (a persistent-cache hit or miss is one
    request to compile an executable): ``executor.compile_count()`` counts
    executor entries and misses recompiles underneath one (PR 21)."""

    def __init__(self):
        self.n = 0

    def __call__(self, event, **_):
        if event in COMPILE_EVENTS:
            self.n += 1


class Context:
    """What a driver gets: the cell's data, the place, and the clocks."""

    def __init__(self, registry, cell, seed, seconds, trace, place, born,
                 compile_counter, device_kind):
        self.registry = registry
        self.config = registry.config(cell["config"])
        self.traffic = registry.traffic(cell["traffic"])
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.place = place
        self.spans = profiling.Spans()
        self.tracer = profiling.Tracer()
        self._born = born
        self._compiles = compile_counter
        self.device_kind = device_kind

    def since_start(self):
        return time.perf_counter() - self._born

    def compiles(self):
        return self._compiles.n

    def peak(self, key):
        return peaks.peak(self.device_kind, key)

    @staticmethod
    def log(msg):
        print(msg, flush=True)


def run_cell(name, seed, seconds, trace, place, root=ROOT, born=None):
    """Run cell ``name`` on ``place``; returns the result object (the last
    line's content).  ``born`` is the perf_counter instant the process
    started, for ``setup_s``."""
    import jax
    import jax.monitoring

    import paddle_tpu as fluid

    born = time.perf_counter() if born is None else born
    registry = Registry(root)
    cell = registry.cell(name)
    device = place.jax_device()
    fluid.enable_compilation_cache()
    counter = CompileCounter()
    jax.monitoring.register_event_listener(counter)
    ctx = Context(registry, cell, seed, seconds, bool(trace), place, born,
                  counter, device.device_kind)
    ctx.log("cell %s: config %s, traffic %s, seed %d, %.0f s, trace %d; "
            "compile cache %s" % (name, cell["config"], cell["traffic"], seed,
                                  seconds, trace,
                                  jax.config.jax_compilation_cache_dir))
    try:
        res = registry.module("drivers", ctx.config["driver"]).run(ctx)
    finally:
        jax.monitoring.unregister_event_listener(counter)
    if device.platform == "tpu" and "flops_per_step" in res["observed"]:
        o = res["observed"]
        ctx.log("model-FLOP utilization end to end: %.2f%% of the %s bf16 peak"
                % (100 * o["flops_per_step"] * o["steps"] / o["window_s"]
                   / ctx.peak("bf16_flops"), device.device_kind))
    observed = dict(res["observed"], config=ctx.config, traffic=ctx.traffic,
                    peak=ctx.peak)
    out = {"correct": bool(res["correct"]), "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": {}}
    stats = device.memory_stats() or {}
    ctx.log("device memory: %s" % json.dumps(stats))
    # the TPU runtime counts arrays (peak_bytes_in_use) and the compiled
    # programs' temporaries (peak_bytes_reserved) apart; a step with 6 GB of
    # temporaries shows 1.4 GB in the first alone (PR 23)
    out["device"] = {"platform": device.platform, "kind": device.device_kind,
                     "count": len(jax.devices()),
                     "memory_peak_bytes": int(
                         stats.get("peak_bytes_in_use", 0)
                         + stats.get("peak_bytes_reserved", 0))}
    if not trace:
        for m in registry.metrics("end_to_end", name):
            out["metrics"][m["name"]] = {
                "value": float(res["end_to_end"][m["name"]]), "unit": m["unit"]}
        return out
    tr = observed.get("trace")
    if trace_reduce.usable(tr):
        busy, window = trace_reduce.busy_and_window_s(tr)
        observed.update(busy_s=busy, traced_window_s=window)
        out["device"].update(busy_s=busy, window_s=window)
        out["breakdown"] = {"device_ops": trace_reduce.top_device_ops(tr, 10),
                            "idle_gaps": trace_reduce.idle_gaps(tr, 10)}
    for m in registry.metrics("per_layer", name):
        value = registry.module("layer_metrics", m["name"]).read(observed)
        if value is not None:
            out["metrics"][m["name"]] = {"value": float(value),
                                         "unit": m["unit"]}
    return out


def main(argv=None):
    born = time.perf_counter() - process_age_s()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    import paddle_tpu as fluid

    registry = Registry(ROOT)
    cell = registry.cell(args.workload)
    devices = jax.devices()
    print("device: platform=%s kind=%s count=%d jax=%s" % (
        devices[0].platform, devices[0].device_kind, len(devices),
        jax.__version__), flush=True)
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("need %d TPU chip(s), found %s: no result"
              % (cell["chips"], [str(d) for d in devices]), flush=True)
        return 1
    seconds = (registry.bench["run_seconds"] if args.seconds is None
               else args.seconds)
    out = run_cell(args.workload, args.seed, seconds, args.trace,
                   fluid.TPUPlace(), born=born)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
