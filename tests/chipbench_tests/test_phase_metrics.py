"""The per-layer metrics that read the program's always-on phase cells
(PR 24): each reader gets a cell seeded with known observations and returns
the mean, sum or ratio it should, and ``None`` where the cell is empty (what an
older commit of the program gives: the result line then leaves the metric
out).  No test needs a chip."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.registry import Registry  # noqa: E402
from paddle_tpu import observability as obs  # noqa: E402

# metric -> (cells it reads with the seconds seeded into each, expected value)
MEAN_MS = {
    "sched_iteration_ms": "serving.decode.iteration",
    "sched_host_ms": "serving.decode.iteration.host",
    "decode_wait_ms": "serving.decode.step.wait",
    "prefill_chunk_ms": "serving.decode.prefill",
    "admit_ms": "serving.decode.admit",
    "exe_run_ms": "executor.run",
    "exe_launch_ms": "executor.dispatch",
    "exe_prepare_feed_ms": "executor.prepare_feed",
    "prefetch_wait_ms": "prefetch.wait",
}
CASES = {name: ({cell: [0.010, 0.020, 0.045]}, 25.0)
         for name, cell in MEAN_MS.items()}
CASES["chunk_iteration_share_pct"] = (
    {"serving.decode.prefill": [0.05], "serving.decode.iteration": [0.04] * 4},
    25.0)
CASES["setup_warmup_s"] = (
    {"serving.decode.warmup": [40.0, 2.5], "serving.model_load": [0.5]}, 43.0)
CASES["setup_compile_s"] = ({"executor.first_run": [1.5, 12.0]}, 13.5)
ALL_CELLS = sorted({c for seeded, _ in CASES.values() for c in seeded})


@pytest.fixture
def empty_cells():
    """The cells the readers use, emptied for the test and put back after."""
    tel = obs.get_telemetry()
    cells = {c: tel.histogram(c) for c in ALL_CELLS}
    for h in cells.values():
        h._reset()
    yield cells
    for h in cells.values():
        h._reset()


def _reader(name):
    return Registry(ROOT).module("layer_metrics", name).read


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_returns_what_the_cell_holds(name, empty_cells):
    seeded, want = CASES[name]
    for cell, seconds in seeded.items():
        for s in seconds:
            empty_cells[cell].observe(s)
    assert _reader(name)({}) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_returns_none_where_the_cell_is_empty(name, empty_cells):
    assert _reader(name)({}) is None


def test_the_twelve_are_in_benchmark_json_with_their_cells():
    reg = Registry(ROOT)
    entries = {m["name"]: m for m in reg.bench["per_layer"]}
    train = ["tfbase_train_s256", "tfbase_train_s2048"]
    for name in CASES:
        m = entries[name]
        assert m["better"] == "lower"
        assert m["source"] == ("program_counter"
                               if name == "chunk_iteration_share_pct"
                               else "program_span")
        serve = name.startswith(("sched_", "decode_", "prefill_", "chunk_",
                                 "admit_")) or name == "setup_warmup_s"
        # the cells of PR 24 first and in their order; what a later PR appends
        # is a cell of the benchmark that reports the metric this one moves
        first = ["tfbase_lm_chat"] if serve else train
        assert m["workloads"][:len(first)] == first
        for cell in m["workloads"][len(first):]:
            assert reg.cell(cell) and m["moves"] in {
                e["name"] for e in reg.metrics("end_to_end", cell)}, (name, cell)
    assert entries["setup_warmup_s"]["moves"] == "setup_s"
    assert entries["setup_compile_s"]["moves"] == "setup_s"
    assert entries["admit_ms"]["moves"] == "serve_tokens_per_s"
