"""The eight per-layer metrics that read the serving loop's own account
(PR 38): after a toy serving run each reader gives a float, a quiet process
reads 0.0 and never ``None`` (a ``null`` on a result line cannot be compared),
a program WITHOUT the cell reads ``None`` (the line then leaves the metric
out), and ``BENCHMARK.json`` lists each with at least the cells of its day.
The issue's ``stall_count``, ``stall_s`` and ``step_interval_max_ms`` are held
back (``chipbench/loop_cells.py`` says why).  No test needs a chip."""
import gc
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import paddle_tpu as fluid  # noqa: E402
from chipbench import contract, loop_cells, run  # noqa: E402
from chipbench.registry import Registry  # noqa: E402
from paddle_tpu import observability as obs  # noqa: E402
from test_chipbench import toy_root  # noqa: E402,F401 — the toy checkout

SERVING = ["tfbase_lm_chat", "sala_longctx_decode", "kanana2_standing_decode",
           "mellum2_standing_mixedctx"]
# metric -> (unit, better, source, moves, cells)
EIGHT = {
    "host_gc_s": ("s", "lower", "program_span", "serve_tokens_per_s", SERVING),
    "loop_unaccounted_pct": ("%", "lower", "program_span", "itl_p95_ms",
                             SERVING),
    "step_build_ms": ("ms", "lower", "program_span", "itl_p95_ms", SERVING),
    "step_dispatch_ms": ("ms", "lower", "program_span", "itl_p95_ms", SERVING),
    "step_commit_ms": ("ms", "lower", "program_span", "itl_p95_ms", SERVING),
    "steps_overlapped_pct": ("%", "higher", "program_counter", "itl_p95_ms",
                             SERVING),
    "chunk_program_ms": ("ms", "lower", "program_span", "itl_p95_ms", SERVING),
    "history_chunk_tokens_per_s": ("tokens/s", "higher", "program_span",
                                   "setup_s", SERVING[1:]),
}
STANDING_SETUP = {"setup": {"prompt_tokens": 4096}}


def _read(name, observed=None):
    read = Registry(ROOT).module("layer_metrics", name).read
    return read(STANDING_SETUP if observed is None else observed)


@pytest.fixture(scope="module")
def served(toy_root):  # noqa: F811
    """The toy serving run that is there (``tfbase_lm_chat`` at toy widths on
    the CPU), traced: its result line, with the cells it left behind."""
    return run.run_cell("tfbase_lm_chat", 11, 1.5, 1, fluid.CPUPlace(),
                        root=toy_root)


def test_a_traced_serving_line_carries_every_one_of_its_cells(served):
    assert served["correct"] is True
    for name, (unit, *_rest, cells) in EIGHT.items():
        if "tfbase_lm_chat" not in cells:
            assert name not in served["metrics"]
            continue
        m = served["metrics"][name]
        assert m["unit"] == unit and isinstance(m["value"], float), name
        assert m["value"] >= 0.0
    # the loop ran: its phases took time, and nearly all of it lies in spans
    for name in ("step_build_ms", "step_dispatch_ms", "step_commit_ms",
                 "chunk_program_ms"):
        assert served["metrics"][name]["value"] > 0, name
    assert served["metrics"]["loop_unaccounted_pct"]["value"] < 25
    assert 0 < served["metrics"]["steps_overlapped_pct"]["value"] <= 100
    # the older phase metrics are still on the line
    assert {"sched_host_ms", "sched_iteration_ms", "decode_wait_ms",
            "prefill_chunk_ms", "decode_step_ms"} <= set(served["metrics"])


@pytest.mark.parametrize("name", sorted(EIGHT))
def test_each_reader_gives_a_float_after_the_run(served, name):
    value = _read(name)
    assert isinstance(value, float) and value >= 0.0
    if name == "history_chunk_tokens_per_s":
        chunk_s = obs.histogram("serving.decode.prefill.chunk").snapshot().sum
        assert value == pytest.approx(4096 / chunk_s)
        assert _read(name, {}) == 0.0            # no set-up observed: no tokens


@pytest.mark.parametrize("name, cells", [
    ("host_gc_s", ['host.gc{gen="%d"}' % g for g in range(3)]),
    ("chunk_program_ms", ["serving.decode.prefill.chunk"]),
    ("history_chunk_tokens_per_s", ["serving.decode.prefill.chunk"]),
    ("step_build_ms", ["serving.decode.step.build"]),
])
def test_a_quiet_process_reads_zero_and_not_none(served, name, cells):
    """No collection, no chunk, no step: the cell is there and empty."""
    gc.disable()
    try:
        for c in cells:
            obs.histogram(c)._reset()
        value = _read(name)
    finally:
        gc.enable()
    assert value == 0.0 and isinstance(value, float)


def test_a_collection_is_read_back(served):
    obs.watch_gc()
    g0 = _read("host_gc_s")
    gc.collect(2)
    assert _read("host_gc_s") > g0


def test_a_program_without_the_cell_reads_none(monkeypatch):
    """What the parent commit gives: the line leaves the metric out."""
    bare = obs.Telemetry(enabled=True)
    monkeypatch.setattr(loop_cells, "_telemetry", lambda: bare)
    for name in EIGHT:
        assert _read(name) is None, name


def test_the_eight_are_in_benchmark_json_with_their_cells():
    """Each is there with what PR 38 gave it; later PRs append metrics behind
    them and cells to their lists, which is none of this test's business."""
    reg = Registry(ROOT)
    entries = {m["name"]: m for m in reg.bench["per_layer"]}
    for name, (unit, better, source, moves, cells) in EIGHT.items():
        entry = dict(entries[name])
        listed = entry.pop("workloads")
        assert entry == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": "serving scheduler", "moves": moves}
        assert set(cells) <= set(listed), name
        for cell in listed:
            assert moves in {e["name"]
                             for e in reg.metrics("end_to_end", cell)}
    assert contract.violations(ROOT) == []


def test_contract_main_exits_zero(capsys):
    assert contract.main([ROOT]) == 0
    assert capsys.readouterr().out == "0 violations\n"
