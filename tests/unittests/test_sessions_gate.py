"""Tier-1 wiring for the conversational-sessions gate: run
tools/check_sessions.py (3-turn warm-vs-cold bitwise with the
leaked-refcount sweep, affinity hit-rate beating least-loaded,
kill-session-owner-mid-conversation bitwise resume on a sibling,
affinity-vs-health fallback under draining/quiesce, and prefill/decode
role-specialized handoff) in a clean subprocess on CPU and fail on any
regression, so session KV persistence can't silently lose its
correctness or leak-freedom contracts."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_sessions_gate():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_PLATFORM_NAME"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "check_sessions.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (
        "check_sessions failed:\nstdout:\n%s\nstderr:\n%s"
        % (proc.stdout, proc.stderr))
    assert "sessions gate OK" in proc.stdout
