"""Tier-1 wiring for the decode gate: the scenarios of tools/check_decode.py
(bitwise continuous-vs-per-sequence token equality with the zero-recompile and
free-on-retire asserts, generate-path admission contracts, the
serving.decode.* telemetry schema, chunked prefill and the prefix cache, and
what makes each fast as COUNTS: tokens a decode step, iterations to a short
prompt's first token behind a long prefill, prompt tokens prefilled and pages
hit), one case each, so iteration-level decode can't rot."""
import _gate


@_gate.scenarios("check_decode")
def test_decode_gate(scenario):
    assert "OK" in scenario()
