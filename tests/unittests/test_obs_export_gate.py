"""Tier-1 wiring for the observability export gate: run
tools/check_obs_export.py (histogram quantile accuracy vs exact
percentiles with merge/window laws, /metrics Prometheus exposition
parseability with monotone bucket ladders + /healthz readiness probe,
per-request trace-tree propagation under load with injected retries,
SLO-breach alert emission moving the desired-replicas autoscale signal,
and the always-on path's cost in function calls), one case a scenario,
and fail on any regression, so the serving signal plane can't rot."""
import _gate


# scenario_metrics_export reads the WHOLE process's registry through /metrics:
# in a worker that has served other tests, labelled cells of the histograms it
# checks (a class, a model) fall into its bucket ladders and they stop being
# monotone.  It takes 4 s alone.
@_gate.scenarios("check_obs_export",
                 apart={"scenario_metrics_export": 60})
def test_obs_export_gate(scenario):
    assert "OK" in scenario()
