#!/usr/bin/env python
"""Roofline / attribution perf report for a compiled step.

Extends ``profiler.compiled_op_report`` (per-op HLO instruction / output
-bytes attribution of the fused executable) with the compute-introspection
plane's numbers (``observability.xla_stats``): program flops, bytes
accessed, arithmetic intensity, the device's machine balance, a
memory- vs compute-bound roofline verdict, the exact HBM footprint
breakdown, and — from a measured executor run — step time, MFU and
HBM-bandwidth utilization.  This is the report PERF.md's methodology
note points every future speed claim at: one command, one table, flops
and bytes from XLA's own analyses rather than hand arithmetic.

Usage:
  python tools/perf_report.py                         # default: train_mlp
  python tools/perf_report.py --bench eval_mlp --iters 50
  python tools/perf_report.py --peak-flops 275e12 --peak-bw 1.228e12
  python tools/perf_report.py --json /tmp/report.json

The built-in benches come from benchmarks/compute_benches.py (shared
with tools/check_perf_drift.py); :func:`report_program` is importable
for arbitrary programs.  CPU numbers are for the report *plumbing* —
roofline verdicts worth publishing come from a TPU run with the real
peak table (observability.xla_stats.PEAK_TABLE).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

if "JAX_PLATFORMS" not in os.environ and "JAX_PLATFORM_NAME" not in os.environ:
    os.environ["JAX_PLATFORMS"] = "cpu"


def _fmt_bytes(n):
    for unit, factor in (("GB", 1 << 30), ("MB", 1 << 20), ("KB", 1 << 10)):
        if n >= factor:
            return "%.2f %s" % (n / factor, unit)
    return "%d B" % n


def report_program(program, startup, feed, fetch_list, iters=20,
                   peak_flops=None, peak_membw=None):
    """Measure + introspect one program's step; returns (text, data)."""
    import paddle_tpu as fluid
    from paddle_tpu import profiler
    from paddle_tpu.observability import xla_stats

    xla_stats.reset()
    xla_stats.enable(peak_flops=peak_flops, peak_membw=peak_membw)
    try:
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            times = []
            for i in range(iters):
                t0 = time.perf_counter()
                outs = exe.run(program, feed=feed, fetch_list=fetch_list)
                for o in outs:      # lazy fetches: the step ends here
                    np.asarray(o)
                times.append(time.perf_counter() - t0)
            state = exe._collect_state(program, scope)
        st = xla_stats.program_stats(
            "%x:v%d" % (id(program), getattr(program, "version", 0)))
    finally:
        # the overrides outlive disable() by design; a report must not
        # leave its pinned roof behind for the next in-process caller
        xla_stats.disable()
        xla_stats.restore_defaults()
    if st is None:
        raise RuntimeError("xla_stats captured nothing — backend without "
                           "cost/memory analysis?")
    # steady-state step time: drop the compile step, take the median
    steady = sorted(times[1:] or times)
    step_s = steady[len(steady) // 2]
    pf, pb = xla_stats.device_peaks(st.device_kind)
    if peak_flops is not None:
        pf = float(peak_flops)
    if peak_membw is not None:
        pb = float(peak_membw)
    ndev = st.num_devices
    intensity = st.arith_intensity
    balance = (pf / pb) if pb else None
    bound_by = None
    if intensity is not None and balance is not None:
        bound_by = "compute" if intensity >= balance else "memory"
    mfu = st.flops / step_s / (pf * ndev) if pf else None
    bw_util = st.bytes_accessed / step_s / (pb * ndev) if pb else None

    # per-op attribution of the same step (its own AOT compile through
    # profiler.compile_step; the executor's executable was captured above)
    op_report, op_rows = profiler.compiled_op_report(
        program, feed, state=state, fetch_list=fetch_list,
        sorted_key="out_bytes")

    data = {
        "device_kind": st.device_kind,
        "num_devices": ndev,
        "flops_per_step": st.flops,
        "bytes_accessed": st.bytes_accessed,
        "arith_intensity": intensity,
        "machine_balance": balance,
        "bound_by": bound_by,
        "peak_flops_per_device": pf,
        "peak_membw_per_device": pb,
        "peak_hbm_bytes": st.peak_hbm_bytes,
        "arg_bytes": st.arg_bytes,
        "output_bytes": st.out_bytes,
        "temp_bytes": st.temp_bytes,
        "code_bytes": st.code_bytes,
        "step_time_s": step_s,
        "mfu": mfu,
        "bw_util": bw_util,
        "iters": iters,
        "op_rows": op_rows,
    }

    lines = []
    lines.append("== roofline ==")
    lines.append("device           : %s x%d" % (st.device_kind, ndev))
    lines.append("flops/step       : %.4g" % st.flops)
    lines.append("bytes accessed   : %.4g (%s)"
                 % (st.bytes_accessed, _fmt_bytes(st.bytes_accessed)))
    lines.append("arith intensity  : %s flops/byte"
                 % ("%.3f" % intensity if intensity is not None else "-"))
    lines.append("machine balance  : %s flops/byte  (peak %.3g FLOP/s, "
                 "%.3g B/s per device)"
                 % ("%.3f" % balance if balance is not None else "-", pf, pb))
    lines.append("bound by         : %s" % (bound_by or "-"))
    lines.append("== memory ==")
    lines.append("peak HBM         : %s  (args %s + outputs %s + temp %s)"
                 % (_fmt_bytes(st.peak_hbm_bytes), _fmt_bytes(st.arg_bytes),
                    _fmt_bytes(st.out_bytes), _fmt_bytes(st.temp_bytes)))
    lines.append("== measured (median of %d steady steps) ==" % len(steady))
    lines.append("step time        : %.6f s" % step_s)
    lines.append("MFU              : %s"
                 % ("%.2f%%" % (100 * mfu) if mfu is not None else "-"))
    lines.append("HBM BW util      : %s"
                 % ("%.2f%%" % (100 * bw_util) if bw_util is not None else "-"))
    lines.append("== per-op (compiled instructions; out-bytes sorted) ==")
    lines.append(op_report)
    return "\n".join(lines), data


def main():
    import compute_benches as cb

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--bench", default="train_mlp",
                    choices=("train_mlp", "eval_mlp"))
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--peak-flops", type=float, default=None,
                    help="per-device peak FLOP/s roof override")
    ap.add_argument("--peak-bw", type=float, default=None,
                    help="per-device peak HBM B/s roof override")
    ap.add_argument("--json", default=None, help="also dump data as JSON")
    args = ap.parse_args()

    if args.bench == "train_mlp":
        main_p, startup, loss, feed = cb.build_mlp_train(batch=args.batch)
        fetch = [loss]
    else:
        main_p, startup, out, feed = cb.build_mlp_eval(batch=args.batch)
        fetch = [out]

    text, data = report_program(main_p, startup, feed, fetch,
                                iters=args.iters,
                                peak_flops=args.peak_flops,
                                peak_membw=args.peak_bw)
    print("perf report: %s (batch %d)" % (args.bench, args.batch))
    print(text)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(data, f, indent=2, default=str)
        print("json -> %s" % args.json)
    return 0


if __name__ == "__main__":
    sys.exit(main())
