"""Tier-1 wiring for the multi-model serving-plane gate: run
tools/check_router.py (a two-deployment ModelRouter over forced host
devices: per-model outputs bitwise-identical to dedicated single-model
pools, tenant token-bucket + in-flight breaches typed
ServingQuotaExceeded with the labeled quota_rejections counter
advancing, a 0.75/0.25 canary split exact within +/-1 over a seeded
run plus one-call rollback, and cold activate / LRU deactivate under
live traffic with zero dropped futures and bitwise parked answers) in
a clean subprocess on CPU and fail on any regression, so the serving
plane can't rot."""
import _gate


@_gate.scenarios("check_router")
def test_model_router_gate(scenario):
    assert "OK" in scenario()
