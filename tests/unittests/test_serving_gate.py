"""Tier-1 wiring for the serving gate: run tools/check_serving.py
(bitwise batched-vs-unbatched equality on both backends, deadline and
backpressure behavior, hot swap with drain under load, serving.*
telemetry schema, and the bench_serving >=2x batching-throughput smoke)
in a clean subprocess on CPU and fail on any regression, so the dynamic
batching engine can't rot."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_serving_gate():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_PLATFORM_NAME"] = "cpu"
    env.pop("PADDLE_TPU_TELEMETRY", None)  # gate needs telemetry enabled
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "check_serving.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (
        "check_serving failed:\nstdout:\n%s\nstderr:\n%s"
        % (proc.stdout, proc.stderr))
    assert "serving gate OK" in proc.stdout
