"""Tier-1 wiring for the observability export gate: run
tools/check_obs_export.py (histogram quantile accuracy vs exact
percentiles with merge/window laws, /metrics Prometheus exposition
parseability with monotone bucket ladders + /healthz readiness probe,
per-request trace-tree propagation under load with injected retries,
SLO-breach alert emission moving the desired-replicas autoscale signal,
and the always-on-path overhead budget) in a clean subprocess on CPU
and fail on any regression, so the serving signal plane can't rot."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_obs_export_gate():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_PLATFORM_NAME"] = "cpu"
    env.pop("PADDLE_TPU_TELEMETRY", None)  # gate needs telemetry enabled
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "check_obs_export.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (
        "check_obs_export failed:\nstdout:\n%s\nstderr:\n%s"
        % (proc.stdout, proc.stderr))
    assert "observability export gate OK" in proc.stdout
