"""The eight per-layer metrics that read set-up's own account (PR 54:
``obs.watch_compiles()``, the span ``serving.decode.build`` and its parts, the
gauge ``process.import_done_s``): the CPU rehearsal's traced lines carry them
(eight on a serving cell, five on a training cell), each reader gives a float
after a run, 0.0 and never ``None`` where nothing was observed, ``None`` on a
program without the watcher (what the parent commit gives: the line then
leaves the metric out), and ``BENCHMARK.json`` lists each with the cells of
its day.  No test needs a chip."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import paddle_tpu as fluid  # noqa: E402
from chipbench import contract, loop_cells, run  # noqa: E402
from chipbench.registry import Registry  # noqa: E402
from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu.observability import startup  # noqa: E402
from test_chipbench import toy_root  # noqa: E402,F401 — the toy checkout

TRAIN = ["tfbase_train_s256", "tfbase_train_s2048", "tfbase_train_s4096"]
SERVING = ["tfbase_lm_chat", "sala_longctx_decode", "kanana2_standing_decode",
           "mellum2_standing_mixedctx", "solar2_standing_decode",
           "trinity_open_mixedlen", "evabyte_standing_decode",
           "glm5_standing_dsactx"]
ALL = TRAIN[:1] + SERVING[:1] + TRAIN[1:2] + SERVING[1:2] + TRAIN[2:] + SERVING[2:]
# metric -> (unit, source, layer, moves, cells)
EIGHT = {
    "setup_import_s": ("s", "program_span", "set-up", "setup_s", ALL),
    "setup_trace_lower_s": ("s", "program_span", "set-up", "setup_s", ALL),
    "setup_backend_compile_s": ("s", "program_span", "set-up", "setup_s", ALL),
    "setup_cache_miss_count": ("count", "program_counter", "set-up",
                               "setup_s", ALL),
    "setup_cache_retrieval_s": ("s", "program_span", "set-up", "setup_s", ALL),
    "setup_scheduler_build_s": ("s", "program_span", "set-up", "setup_s",
                                SERVING),
    "setup_scheduler_unspanned_pct": ("%", "program_span", "set-up",
                                      "setup_s", SERVING),
    "loop_compile_requests": ("count", "program_counter", "serving scheduler",
                              "itl_p95_ms", SERVING),
}
FIVE = sorted(n for n, e in EIGHT.items() if e[-1] is ALL)


def _read(name):
    return Registry(ROOT).module("layer_metrics", name).read({})


STAGES = ("xla.compile.trace", "xla.compile.lower", "xla.compile.backend")


def _inside_and_whole(span):
    """Seconds of compile spans put down to ``span``, and the span's own."""
    return (sum(obs.histogram(c, {"within": span}).snapshot().sum
                for c in STAGES), obs.histogram(span).snapshot().sum)


def _run(toy, cell, seed, seconds, span):
    """The cell's traced line at toy widths, and what ``span`` and the
    compile spans inside it gained over the run (the worker's other test
    files have been at the process's cells)."""
    age = obs.gauge("process.import_done_s")
    if age.value is None:       # a test file before this one reset the lot
        age.set(startup.process_age_s())
    before = _inside_and_whole(span)
    loop0 = obs.counter("xla.compile.requests",
                        {"within": "serving.decode.iteration"}).value
    line = run.run_cell(cell, seed, seconds, 1, fluid.CPUPlace(), root=toy)
    return line, tuple(a - b for a, b in zip(
        _inside_and_whole(span), before)) + (loop0,)


@pytest.fixture(scope="module")
def served(toy_root):  # noqa: F811
    return _run(toy_root, "tfbase_lm_chat", 2 ** 31 + 54, 1.5,
                "serving.decode.warmup")


@pytest.fixture(scope="module")
def trained(toy_root):  # noqa: F811
    return _run(toy_root, "tfbase_train_s256", 54, 1.0, "executor.first_run")


def test_a_traced_serving_line_carries_all_eight(served):
    served, (inside, warm, loop0) = served
    assert served["correct"] is True
    for name, (unit, *_rest) in EIGHT.items():
        m = served["metrics"][name]
        assert m["unit"] == unit and isinstance(m["value"], float), name
        assert m["value"] >= 0.0, name
    v = {n: served["metrics"][n]["value"] for n in EIGHT}
    # the engine was built in this process: its construction took time, its
    # named parts are most of it, and its warm-up traced and compiled
    assert v["setup_scheduler_build_s"] > 0
    assert 0 <= v["setup_scheduler_unspanned_pct"] < 100
    assert v["setup_trace_lower_s"] > 0 and v["setup_backend_compile_s"] > 0
    assert v["setup_import_s"] > 0
    # the tests' jax keeps no persistent cache: no miss, no retrieval
    assert v["setup_cache_miss_count"] == v["setup_cache_retrieval_s"] == 0
    # warm-up's menu held: the loop compiled nothing (the reading is over
    # the process: what a test file before this one provoked is in it)
    assert v["loop_compile_requests"] == loop0
    # the coarser two of the same layer are still on the line, and hold the
    # finer ones: a second is counted once
    assert 0 < inside <= warm
    assert served["metrics"]["setup_warmup_s"]["value"] > 0


def test_a_traced_training_line_carries_the_five(trained):
    trained, (inside, first_runs, _) = trained
    assert trained["correct"] is True
    for name in FIVE:
        m = trained["metrics"][name]
        assert m["unit"] == EIGHT[name][0] and m["value"] >= 0.0, name
    for name in set(EIGHT) - set(FIVE):
        assert name not in trained["metrics"], name
    # what ``executor.first_run`` traced, lowered and compiled is no more
    # than the span itself (``setup_compile_s`` on the same line)
    assert 0 < inside <= first_runs
    assert trained["metrics"]["setup_compile_s"]["value"] > 0


@pytest.mark.parametrize("name", sorted(EIGHT))
def test_each_reader_gives_a_float_after_the_run(served, name):
    value = _read(name)
    assert isinstance(value, float) and value >= 0.0


@pytest.mark.parametrize("name", ["setup_trace_lower_s",
                                  "setup_backend_compile_s",
                                  "setup_cache_miss_count",
                                  "setup_cache_retrieval_s",
                                  "loop_compile_requests"])
def test_a_quiet_process_reads_zero_and_not_none(served, name):
    """Armed and nothing compiled (a warm line's misses): the cells are there
    and empty."""
    obs.watch_compiles()
    obs.reset("xla.compile.")
    value = _read(name)
    assert value == 0.0 and isinstance(value, float)


def test_what_compiles_under_no_program_span_is_nobodys(served):
    """The drivers' own compiles after the window (the check's references)
    are ``within="other"``: no set-up metric moves."""
    import jax
    import jax.numpy as jnp

    obs.watch_compiles()
    before = {n: _read(n) for n in EIGHT}
    other = obs.counter("xla.compile.requests", {"within": "other"})
    n0 = other.value
    jax.jit(lambda x: jnp.cos(x) * 54.0)(jnp.ones((3,)))
    assert other.value > n0     # the program, and the eager ``ones``
    assert {n: _read(n) for n in EIGHT} == before


def test_another_span_named_model_load_moves_no_unspanned_share(served):
    """Six of the benchmark's model files time ``make_params`` under the name
    ``serving.model_load``, outside any construction: the share is the
    program's own cell over the whole, and stays what it was."""
    before = _read("setup_scheduler_unspanned_pct")
    with obs.span("serving.model_load", model="somebody-elses-weights"):
        pass
    obs.histogram("serving.model_load").observe(1e3)
    assert _read("setup_scheduler_unspanned_pct") == before
    assert 0 <= before < 100


def test_a_program_without_the_watcher_reads_none(monkeypatch):
    """What the parent commit gives: the line leaves the metric out."""
    bare = obs.Telemetry(enabled=True)
    monkeypatch.setattr(loop_cells, "_telemetry", lambda: bare)
    for name in EIGHT:
        assert _read(name) is None, name


def test_the_eight_are_in_benchmark_json_with_their_cells():
    """Each is there with what PR 54 gave it; later PRs append cells to the
    lists, which is none of this test's business."""
    reg = Registry(ROOT)
    entries = {m["name"]: m for m in reg.bench["per_layer"]}
    for name, (unit, source, layer, moves, cells) in EIGHT.items():
        entry = dict(entries[name])
        listed = entry.pop("workloads")
        assert entry == {"name": name, "unit": unit, "better": "lower",
                         "source": source, "layer": layer, "moves": moves}
        assert set(cells) <= set(listed), name
        for cell in listed:
            assert moves in {e["name"]
                             for e in reg.metrics("end_to_end", cell)}
    assert contract.violations(ROOT) == []
