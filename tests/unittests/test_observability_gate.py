"""Tier-1 wiring for the observability gate: run tools/check_observability.py
(JSONL step-record schema over a real training run, Chrome-trace export
with visible prefetch/dispatch overlap, bitwise telemetry-on/off
neutrality, the always-on span cost budget) in a clean subprocess on CPU
and fail on any regression, so the telemetry subsystem can't rot."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_observability_gate():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_PLATFORM_NAME"] = "cpu"
    env.pop("PADDLE_TPU_TELEMETRY", None)  # gate needs telemetry enabled
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "check_observability.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (
        "check_observability failed:\nstdout:\n%s\nstderr:\n%s"
        % (proc.stdout, proc.stderr))
    assert "observability gate OK" in proc.stdout
