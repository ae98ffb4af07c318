"""Tier-1 wiring for the durable-decode gate: run
tools/check_decode_resilience.py (kill-one-of-4-replicas mid-decode with
bitwise journal replay on siblings, supervisor revival + provable
re-claim, corrupt_kv_page isolation under prefix sharing, decode-step
transient retry, cancel(), replay-budget exhaustion, and the
reset_pools live-sequence guard), one case a scenario, and fail
on any regression, so pool-routed generation can't silently lose its
failure-recovery contract."""
import _gate


@_gate.scenarios("check_decode_resilience")
def test_decode_resilience_gate(scenario):
    assert "OK" in scenario()
