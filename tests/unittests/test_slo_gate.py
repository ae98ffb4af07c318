"""Tier-1 wiring for the overload-resilience gate: run
tools/check_slo.py (self-healing chaos with retry + poison bisection +
bitwise innocents, circuit-breaker trip/fast-fail/half-open recovery,
dead-worker supervision, deadline-aware admission shedding, and the
bench_load open-loop SLO smoke with its per-class goodput ladder) in a
clean subprocess on CPU and fail on any regression, so the serving
resilience layer can't rot."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_slo_gate():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_PLATFORM_NAME"] = "cpu"
    env.pop("PADDLE_TPU_TELEMETRY", None)  # gate needs telemetry enabled
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "check_slo.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (
        "check_slo failed:\nstdout:\n%s\nstderr:\n%s"
        % (proc.stdout, proc.stderr))
    assert "SLO gate OK" in proc.stdout
