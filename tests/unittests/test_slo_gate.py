"""Tier-1 wiring for the overload-resilience gate: run
tools/check_slo.py (self-healing chaos with retry + poison bisection +
bitwise innocents, circuit-breaker trip/fast-fail/half-open recovery,
dead-worker supervision, deadline-aware admission shedding, and the
bench_load open-loop SLO smoke with its per-class goodput ladder), one
case a scenario, and fail on any regression, so the serving resilience
layer can't rot."""
import _gate


@_gate.scenarios("check_slo")
def test_slo_gate(scenario):
    assert "OK" in scenario()
