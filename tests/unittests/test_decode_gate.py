"""Tier-1 wiring for the decode gate: run tools/check_decode.py (bitwise
continuous-vs-per-sequence token equality with the zero-recompile and
free-on-retire asserts, generate-path admission contracts, the
serving.decode.* telemetry schema, and the bench_decode >=2x
continuous-batching tokens/s smoke) in a clean subprocess on CPU and
fail on any regression, so iteration-level decode can't rot."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_decode_gate():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_PLATFORM_NAME"] = "cpu"
    env.pop("PADDLE_TPU_TELEMETRY", None)  # gate needs telemetry enabled
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "check_decode.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (
        "check_decode failed:\nstdout:\n%s\nstderr:\n%s"
        % (proc.stdout, proc.stderr))
    assert "decode gate OK" in proc.stdout
