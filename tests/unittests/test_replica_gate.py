"""Tier-1 wiring for the multi-replica serving gate: the scenarios of
tools/check_replica_pool.py (4-replica pool over >=4 forced host devices:
bitwise identity vs the single-replica engine on both backends, every replica
of a rotation of 4 serving its quarter of a backlog's rows where a rotation of
1 serves them on one, rolling swap_model under live traffic with zero
failed/hung futures and never-zero ready replicas, replica kill -> typed
failure -> supervisor revive, and the bench_load --scaling goodput ladder),
one case each, so multi-replica serving can't rot."""
import _gate


@_gate.scenarios("check_replica_pool")
def test_replica_pool_gate(scenario):
    assert "OK" in scenario()
