"""Tier-1 wiring for the perf-drift gate: tools/check_perf_drift.py must
pass against the committed PERF_BASELINE.json (deterministic compile /
host-copy / XLA-cost invariants over the shared compute benches), and
must FAIL when a deterministic invariant is perturbed — a gate that
cannot fail guards nothing.  Baseline regen is one command:
``python tools/check_perf_drift.py --write-baseline``.
"""
import json
import os
import subprocess
import sys

import pytest

import _gate

REPO = _gate.REPO
BASELINE = os.path.join(REPO, "PERF_BASELINE.json")
# the gate counts compiles and host copies from a process's first step, so
# every run of it is a process of its own (7 s alone; the limit is for a
# machine with six busy workers)
TIMEOUT = 120


def _run_gate(*args):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_PLATFORM_NAME"] = "cpu"
    return subprocess.run(
        [sys.executable, _gate.tool_path("check_perf_drift")] + list(args),
        env=env, capture_output=True, text=True, timeout=TIMEOUT)


@pytest.mark.parametrize(
    "bench", [name for name, _ in _gate.load("check_perf_drift").SCENARIOS])
def test_perf_drift_gate_passes_on_committed_baseline(bench):
    proc = _run_gate("--bench", bench)
    assert proc.returncode == 0, (
        "perf drift gate failed:\nstdout:\n%s\nstderr:\n%s"
        % (proc.stdout, proc.stderr))
    assert "perf drift gate OK" in proc.stdout


def test_perf_drift_gate_fails_on_perturbed_invariant(tmp_path):
    with open(BASELINE) as f:
        doc = json.load(f)
    # perturb an exact-match invariant: one extra compile = one silent
    # warmup-stall regression, exactly what the gate exists to catch
    assert doc["train_mlp"]["compiles"]["tol"] == 0
    doc["train_mlp"]["compiles"]["value"] += 1
    perturbed = tmp_path / "perturbed_baseline.json"
    perturbed.write_text(json.dumps(doc))
    proc = _run_gate("--baseline", str(perturbed), "--bench", "train_mlp")
    assert proc.returncode == 1, (
        "gate passed a perturbed baseline:\nstdout:\n%s" % proc.stdout)
    assert "DRIFT" in proc.stdout and "compiles" in proc.stdout


def test_partial_regen_merges_instead_of_truncating(tmp_path):
    """--bench X --write-baseline must keep the OTHER benches' committed
    entries — a serving-only regen must not delete the training
    invariants."""
    import shutil

    copy = tmp_path / "baseline.json"
    shutil.copy(BASELINE, copy)
    proc = _run_gate("--bench", "serving_pad", "--write-baseline",
                     "--baseline", str(copy))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(copy.read_text())
    assert "train_mlp" in doc and "eval_mlp" in doc and "serving_pad" in doc
