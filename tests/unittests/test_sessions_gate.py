"""Tier-1 wiring for the conversational-sessions gate: run
tools/check_sessions.py (3-turn warm-vs-cold bitwise with the
leaked-refcount sweep, affinity hit-rate beating least-loaded,
kill-session-owner-mid-conversation bitwise resume on a sibling,
affinity-vs-health fallback under draining/quiesce, and prefill/decode
role-specialized handoff), one case a scenario, and fail on any
regression, so session KV persistence can't silently lose its
correctness or leak-freedom contracts."""
import _gate


@_gate.scenarios("check_sessions")
def test_sessions_gate(scenario):
    assert "OK" in scenario()
