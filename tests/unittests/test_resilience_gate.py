"""Tier-1 wiring for the resilience gate: run tools/check_resilience.py
(torn checkpoint write -> bitwise resume from last-good; injected NaN ->
step skipped) in a clean CPU subprocess and fail on any regression."""
import _gate


@_gate.scenarios("check_resilience")
def test_resilience_gate(scenario):
    assert "OK" in scenario()
