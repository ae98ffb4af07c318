"""Tier-1 wiring for the resilience gate: run tools/check_resilience.py
(torn checkpoint write -> bitwise resume from last-good; injected NaN ->
step skipped) in a clean CPU subprocess and fail on any regression."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_resilience_gate():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_PLATFORM_NAME"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "check_resilience.py")],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, (
        "check_resilience failed:\nstdout:\n%s\nstderr:\n%s"
        % (proc.stdout, proc.stderr))
    assert "resilience gate OK" in proc.stdout
