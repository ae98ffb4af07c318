"""Tier-1 wiring for the durable-decode gate: run
tools/check_decode_resilience.py (kill-one-of-4-replicas mid-decode with
bitwise journal replay on siblings, supervisor revival + provable
re-claim, corrupt_kv_page isolation under prefix sharing, decode-step
transient retry, cancel(), replay-budget exhaustion, and the
reset_pools live-sequence guard) in a clean subprocess on CPU and fail
on any regression, so pool-routed generation can't silently lose its
failure-recovery contract."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_decode_resilience_gate():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_PLATFORM_NAME"] = "cpu"
    env.pop("PADDLE_TPU_TELEMETRY", None)  # gate needs telemetry enabled
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tools", "check_decode_resilience.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (
        "check_decode_resilience failed:\nstdout:\n%s\nstderr:\n%s"
        % (proc.stdout, proc.stderr))
    assert "decode resilience gate OK" in proc.stdout
