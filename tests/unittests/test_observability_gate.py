"""Tier-1 wiring for the observability gate: run tools/check_observability.py
(JSONL step-record schema over a real training run, Chrome-trace export
with visible prefetch/dispatch overlap, bitwise telemetry-on/off
neutrality, the always-on span's cost in function calls), one case a scenario,
and fail on any regression, so the telemetry subsystem can't rot."""
import _gate


@_gate.scenarios("check_observability")
def test_observability_gate(scenario):
    assert "OK" in scenario()
