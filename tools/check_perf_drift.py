#!/usr/bin/env python
"""Perf-drift gate: deterministic per-bench invariants vs a committed baseline.

Wall-clock benchmarks can't gate in CI (shared boxes, thermal noise), so
regressions land silently between the BENCH_* rounds.  This gate guards
the *deterministic shadow* of performance instead — quantities that are
exact for a fixed (program, shapes, jax/XLA version) and that move
whenever the perf-relevant machinery changes:

- ``compiles``           : executor compile-cache misses (the no-recompile
                           contract; a new recompile = a new warmup stall)
- ``feed_host_copies``   : host-side feed copies (the PR-3 zero-copy
                           contract on the fast path)
- ``flops_per_step`` / ``bytes_accessed`` / ``peak_hbm_bytes`` /
  ``arg_bytes`` / ``temp_bytes`` : XLA cost/memory analysis of the
                           compiled step via observability.xla_stats — a
                           jump in bytes-accessed is the HBM-bound
                           regression wall-clock would eventually show
- ``padded_rows`` etc.   : serving bucket-padding waste for a fixed
                           request sequence

Scenarios live in benchmarks/compute_benches.py (shared with
tools/perf_report.py).  Counts compare exactly; analysis-derived bytes
get a relative tolerance so a toolchain bump doesn't cry wolf (the
committed values are regenerated then anyway).

Usage:
  python tools/check_perf_drift.py                     # gate vs PERF_BASELINE.json
  python tools/check_perf_drift.py --write-baseline    # regenerate the baseline
  python tools/check_perf_drift.py --baseline PATH     # gate vs another file
  python tools/check_perf_drift.py --list              # show measured invariants

Wired into tier-1 by tests/unittests/test_perf_drift_gate.py, which also
asserts the gate FAILS on a perturbed baseline.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

if "JAX_PLATFORMS" not in os.environ and "JAX_PLATFORM_NAME" not in os.environ:
    os.environ["JAX_PLATFORMS"] = "cpu"

DEFAULT_BASELINE = os.path.join(REPO, "PERF_BASELINE.json")

# tolerance policy for --write-baseline: counts are exact; XLA
# analysis-derived byte/flop figures get slack for toolchain bumps
_REL_TOL = {
    "flops_per_step": 0.05,
    "bytes_accessed": 0.25,
    "peak_hbm_bytes": 0.25,
    "arg_bytes": 0.25,
    "temp_bytes": 0.35,
}


def _xla_invariants(st):
    return {
        "flops_per_step": st.flops,
        "bytes_accessed": st.bytes_accessed,
        "peak_hbm_bytes": st.peak_hbm_bytes,
        "arg_bytes": st.arg_bytes,
        "temp_bytes": st.temp_bytes,
    }


def scenario_train_mlp():
    """5 SGD steps of the seeded MLP: warmup compiles, fast-path
    host-copy count, and the train step's cost/memory analysis."""
    import paddle_tpu as fluid
    from compute_benches import build_mlp_train
    from paddle_tpu import executor as executor_mod
    from paddle_tpu.observability import xla_stats

    xla_stats.reset()
    xla_stats.enable()
    main, startup, loss, feed = build_mlp_train()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    c0 = executor_mod.compile_count()
    h0 = executor_mod.feed_host_copy_count()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(5):
            out = exe.run(main, feed=feed, fetch_list=[loss])
    assert out and float(out[0]) == float(out[0]), "train step returned NaN"
    st = xla_stats.program_stats(
        "%x:v%d" % (id(main), getattr(main, "version", 0)))
    assert st is not None, "xla_stats captured nothing for the train step"
    inv = {
        "compiles": executor_mod.compile_count() - c0,
        "feed_host_copies": executor_mod.feed_host_copy_count() - h0,
    }
    inv.update(_xla_invariants(st))
    xla_stats.disable()
    return inv


def scenario_eval_mlp():
    """3 inference replays of the seeded eval MLP: one compile total,
    zero-state-output step analysis."""
    import paddle_tpu as fluid
    from compute_benches import build_mlp_eval
    from paddle_tpu import executor as executor_mod
    from paddle_tpu.observability import xla_stats

    xla_stats.reset()
    xla_stats.enable()
    main, startup, out_var, feed = build_mlp_eval()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    c0 = executor_mod.compile_count()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(3):
            out = exe.run(main, feed=feed, fetch_list=[out_var])
    assert out, "eval step returned nothing"
    st = xla_stats.program_stats(
        "%x:v%d" % (id(main), getattr(main, "version", 0)))
    assert st is not None, "xla_stats captured nothing for the eval step"
    inv = {"compiles": executor_mod.compile_count() - c0}
    inv.update(_xla_invariants(st))
    xla_stats.disable()
    return inv


def scenario_serving_pad():
    """Warmed 2-bucket engine served 5 single-row requests one at a
    time: bucket padding waste and the zero-recompile-after-warmup
    contract, independent of batcher timing."""
    import tempfile

    import paddle_tpu as fluid  # noqa: F401 — sets up the package
    from compute_benches import save_serving_model, serving_payloads
    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu import executor as executor_mod

    pad0 = obs.counter("serving.padded_rows").value
    rows0 = obs.counter("serving.batched_rows").value
    batches0 = obs.counter("serving.batches").value
    with tempfile.TemporaryDirectory() as td:
        mdir = save_serving_model(os.path.join(td, "m"))
        eng = serving.InferenceEngine(mdir, batch_buckets=(2, 4),
                                      supervise=False)
        try:
            c_warm = executor_mod.compile_count()
            for p in serving_payloads(5):
                eng.predict({"x": p}, timeout=60)
            compiles_steady = executor_mod.compile_count() - c_warm
        finally:
            eng.stop()
    return {
        "compiles_steady": compiles_steady,
        "padded_rows": obs.counter("serving.padded_rows").value - pad0,
        "batched_rows": obs.counter("serving.batched_rows").value - rows0,
        "batches": obs.counter("serving.batches").value - batches0,
    }


def scenario_decode_prefix():
    """Sequential shared-prefix decode fan-out through a prefix-cached
    scheduler: page hit/miss counts, prompt tokens actually prefilled
    (vs avoided), and the zero-recompile contract with chunked prefill
    enabled — all exact for the seeded workload.  A drop in
    kv_hit_pages or a rise in prefill_tokens is a prefix-cache
    regression long before any wall-clock bench would show it."""
    from compute_benches import build_decode_prefix_model, decode_prefix_prompts
    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu import executor as executor_mod

    model = build_decode_prefix_model()
    prompts = decode_prefix_prompts()
    hit = obs.counter("serving.decode.kv_hit_pages")
    miss = obs.counter("serving.decode.kv_miss_pages")
    pt = obs.counter("serving.decode.prefill_tokens")
    tok = obs.counter("serving.decode.tokens")
    sched = serving.DecodeScheduler(model, serving.DecodeConfig(
        num_slots=2, page_size=8, max_seq_len=64, max_new_tokens=4,
        prefill_chunk_tokens=8, prefix_cache=True))
    c0 = executor_mod.compile_count()
    h0, m0, p0, t0 = hit.value, miss.value, pt.value, tok.value
    for p in prompts:
        sched.generate(p, timeout=300)
    inv = {
        "compiles_steady": executor_mod.compile_count() - c0,
        "kv_hit_pages": hit.value - h0,
        "kv_miss_pages": miss.value - m0,
        "prefill_tokens": pt.value - p0,
        "prefill_tokens_avoided":
            sum(len(p) for p in prompts) - (pt.value - p0),
        "generated_tokens": tok.value - t0,
        "kv_pages_leaked": sched.stats()["kv_pages_used"],
    }
    sched.stop()
    return inv


SCENARIOS = (
    ("train_mlp", scenario_train_mlp),
    ("eval_mlp", scenario_eval_mlp),
    ("serving_pad", scenario_serving_pad),
    ("decode_prefix", scenario_decode_prefix),
)


def measure(only=None):
    results = {}
    for name, fn in SCENARIOS:
        if only and name != only:
            continue
        results[name] = fn()
    return results


def _tolerance_entry(inv_name, value):
    rel = _REL_TOL.get(inv_name)
    if rel is None:
        return {"value": value, "tol": 0}
    return {"value": value, "rel_tol": rel}


def write_baseline(path, results):
    """Write (or, for a --bench partial regen, MERGE into) the baseline:
    benches not measured this run keep their committed entries instead of
    being silently dropped."""
    import jax

    doc = {}
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        pass
    doc["_meta"] = {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "regen": "python tools/check_perf_drift.py --write-baseline",
        "note": "deterministic perf invariants; see tools/check_perf_drift.py",
    }
    for bench, invs in results.items():
        doc[bench] = {k: _tolerance_entry(k, v) for k, v in sorted(invs.items())}
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return doc


def compare(baseline, results):
    """Returns a list of (bench, invariant, measured, expected, tol_abs,
    ok) rows plus a list of structural failure strings."""
    rows, problems = [], []
    for bench, invs in sorted(results.items()):
        base = baseline.get(bench)
        if base is None:
            problems.append(
                "bench %r missing from baseline (regen with "
                "--write-baseline)" % bench)
            continue
        for k, measured in sorted(invs.items()):
            ent = base.get(k)
            if ent is None:
                problems.append(
                    "invariant %s.%s missing from baseline (regen with "
                    "--write-baseline)" % (bench, k))
                continue
            expected = ent["value"]
            tol = (abs(expected) * ent["rel_tol"]
                   if "rel_tol" in ent else ent.get("tol", 0))
            ok = abs(measured - expected) <= tol
            rows.append((bench, k, measured, expected, tol, ok))
        for k in base:
            if k not in invs:
                problems.append(
                    "baseline invariant %s.%s was not measured" % (bench, k))
    return rows, problems


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--write-baseline", action="store_true")
    ap.add_argument("--bench", default=None,
                    help="run only this scenario")
    ap.add_argument("--list", action="store_true",
                    help="measure and print, no gating")
    args = ap.parse_args()

    results = measure(args.bench)

    if args.write_baseline:
        write_baseline(args.baseline, results)
        print("wrote %s:" % args.baseline)
        for bench, invs in sorted(results.items()):
            for k, v in sorted(invs.items()):
                print("  %-12s %-18s %s" % (bench, k, v))
        return 0

    if args.list:
        for bench, invs in sorted(results.items()):
            for k, v in sorted(invs.items()):
                print("%-12s %-18s %s" % (bench, k, v))
        return 0

    try:
        with open(args.baseline) as f:
            baseline = json.load(f)
    except OSError as e:
        print("cannot read baseline %s: %s" % (args.baseline, e))
        print("bootstrap with: python tools/check_perf_drift.py "
              "--write-baseline")
        return 2

    rows, problems = compare(baseline, results)
    failed = [r for r in rows if not r[5]]
    print("%-12s %-18s %16s %16s %12s  %s"
          % ("bench", "invariant", "measured", "baseline", "tol", "status"))
    for bench, k, m, e, tol, ok in rows:
        print("%-12s %-18s %16g %16g %12g  %s"
              % (bench, k, m, e, tol, "ok" if ok else "DRIFT"))
    for p in problems:
        print("STRUCTURE: %s" % p)
    if failed or problems:
        print("perf drift gate FAILED (%d drifted, %d structural)"
              % (len(failed), len(problems)))
        return 1
    print("perf drift gate OK (%d invariants across %d benches)"
          % (len(rows), len(results)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
