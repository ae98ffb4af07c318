#!/usr/bin/env python
"""CI gate for benchmarks/bench_dispatch.py: run it in smoke mode on CPU
and fail on any import/run/assertion error, so the dispatch-overhead
benchmark can't rot.  The smoke pass also asserts fast-path semantics
(bound entry engaged, lazy fetches handed back, bitwise-equal params with
the fast path on and off), so a dispatch regression that changes results
fails here before it ever reaches a perf report.

Runnable locally:
    python tools/check_dispatch_bench.py
and wired into the tier-1 flow via tests/unittests/test_dispatch_bench.py.

Exit code 0 = benchmark ran and its self-checks passed.
"""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_PLATFORM_NAME"] = "cpu"
    # never let the smoke run touch a TPU or its startup hooks
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "bench_dispatch.py"),
         "--smoke"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        sys.stderr.write(proc.stdout)
        sys.stderr.write(proc.stderr)
        sys.stderr.write("\nbench_dispatch.py --smoke FAILED (rc=%d)\n"
                         % proc.returncode)
        return proc.returncode
    # the benchmark prints a JSON report as its last output; parse it so a
    # half-broken run (no report) also fails
    try:
        payload = proc.stdout[proc.stdout.index("{"):]
        report = json.loads(payload)
    except (ValueError, json.JSONDecodeError):
        sys.stderr.write(proc.stdout)
        sys.stderr.write("\nbench_dispatch.py produced no JSON report\n")
        return 1
    missing = [k for k in ("tiny_eval", "tiny_train", "realistic", "prefetch",
                           "telemetry")
               if k not in report]
    if missing:
        sys.stderr.write("report missing regimes: %s\n%s\n"
                         % (missing, proc.stdout))
        return 1
    print("dispatch bench smoke OK: " + ", ".join(
        "%s %.0f steps/s (%.2fx)" % (
            k, report[k]["fast_steps_per_s"], report[k]["speedup"])
        for k in ("tiny_eval", "tiny_train", "realistic"))
        + ", prefetch %.0f->%.0f steps/s (%.2fx overlap)" % (
            report["prefetch"]["sync_steps_per_s"],
            report["prefetch"]["async_steps_per_s"],
            report["prefetch"]["overlap_speedup"])
        + ", telemetry %.2f%% overhead (%d records)" % (
            report["telemetry"]["overhead_pct"],
            report["telemetry"]["records_emitted"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
