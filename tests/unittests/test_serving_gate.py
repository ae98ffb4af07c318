"""Tier-1 wiring for the serving gate: the scenarios of tools/check_serving.py
(bitwise batched-vs-unbatched equality on both backends, deadline/backpressure
contracts, hot swap under load, the serving.* telemetry schema, and what makes
batching fast as COUNTS: rows a dispatch and dispatches a request), one case
each, so the dynamic batcher can't rot."""
import _gate


@_gate.scenarios("check_serving")
def test_serving_gate(scenario):
    assert "OK" in scenario()
