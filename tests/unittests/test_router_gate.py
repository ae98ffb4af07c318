"""Tier-1 wiring for the multi-model serving-plane gate: run
tools/check_router.py (a two-deployment ModelRouter over forced host
devices: per-model outputs bitwise-identical to dedicated single-model
pools, tenant token-bucket + in-flight breaches typed
ServingQuotaExceeded with the labeled quota_rejections counter
advancing, a 0.75/0.25 canary split exact within +/-1 over a seeded
run plus one-call rollback, and cold activate / LRU deactivate under
live traffic with zero dropped futures and bitwise parked answers) in
a clean subprocess on CPU and fail on any regression, so the serving
plane can't rot."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_model_router_gate():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_PLATFORM_NAME"] = "cpu"
    env.pop("PADDLE_TPU_TELEMETRY", None)  # gate needs telemetry enabled
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "check_router.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (
        "check_router failed:\nstdout:\n%s\nstderr:\n%s"
        % (proc.stdout, proc.stderr))
    assert "model router gate OK" in proc.stdout
