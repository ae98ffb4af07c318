"""Tier-1 wiring for the multi-replica serving gate: run
tools/check_replica_pool.py (4-replica pool over >=4 forced host
devices: bitwise identity vs the single-replica engine on both
backends, >=2.5x closed-loop throughput scaling under the slow_execute
shim, rolling swap_model under live traffic with zero failed/hung
futures and never-zero ready replicas, replica kill -> typed failure ->
supervisor revive, and the bench_load --scaling goodput ladder) in a
clean subprocess on CPU and fail on any regression, so multi-replica
serving can't rot."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_replica_pool_gate():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_PLATFORM_NAME"] = "cpu"
    env.pop("PADDLE_TPU_TELEMETRY", None)  # gate needs telemetry enabled
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "check_replica_pool.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (
        "check_replica_pool failed:\nstdout:\n%s\nstderr:\n%s"
        % (proc.stdout, proc.stderr))
    assert "replica pool gate OK" in proc.stdout
