"""The per-layer metric ``chunks_overlapped_pct`` (PR 51): the share of prefill
chunks whose token was read behind a decode step dispatched after them.  The
reader against a scheduler whose chunks have decoding neighbours and one whose
chunks have none, ``None`` on a program without the counters (the line then
leaves the metric out), its entry in ``BENCHMARK.json``, and the traced toy
serving line.  No test needs a chip."""
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import paddle_tpu as fluid  # noqa: E402
from chipbench import contract, loop_cells, run  # noqa: E402
from chipbench.registry import Registry  # noqa: E402
from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu import serving  # noqa: E402
from paddle_tpu.models import transformer as T  # noqa: E402
from test_chipbench import toy_root  # noqa: E402,F401 — the toy checkout

NAME = "chunks_overlapped_pct"
RODE, CHUNKS = ("serving.decode.chunks_overlapped", "serving.decode.prefills")


def _read():
    return Registry(ROOT).module("layer_metrics", NAME).read({})


def _counts():
    return [obs.counter(n).value for n in (RODE, CHUNKS)]


@pytest.fixture(scope="module")
def decode_model():
    params, meta = T.lm_params(seed=7, vocab_size=50, n_layer=2, n_head=2,
                               d_model=32, d_inner=64, max_length=128)
    return T.build_decode_model(params, meta)


@pytest.mark.parametrize("neighbours", [True, False])
def test_the_reader_against_a_run_with_and_without_decoding_neighbours(
        decode_model, neighbours):
    prompts = [np.arange(1, 1 + n, dtype=np.int32) % 49 + 1
               for n in (9, 40, 23)]
    rode0, chunks0 = _counts()
    sched = serving.DecodeScheduler(decode_model, serving.DecodeConfig(
        num_slots=4, page_size=8, max_seq_len=64, max_new_tokens=8,
        prefill_chunk_tokens=16))
    try:
        if neighbours:
            first = sched.submit(prompts[0], max_new_tokens=40)
            while len(first.token_times) < 3:
                time.sleep(0.002)
            futs = [first] + [sched.submit(p) for p in prompts[1:]]
            for f in futs:
                f.result(timeout=120)
        else:
            for p in prompts:
                sched.generate(p, timeout=120)
    finally:
        sched.stop()
    rode, chunks = _counts()
    assert chunks - chunks0 == 1 + 3 + 2
    # every chunk but the first request's own rode a step; alone, none did
    assert rode - rode0 == (5 if neighbours else 0)
    value = _read()
    assert isinstance(value, float) and 0.0 <= value <= 100.0
    assert value == pytest.approx(100.0 * rode / chunks)


@pytest.mark.parametrize("held, want", [
    ((), None), ((RODE,), None), ((CHUNKS,), None), ((RODE, CHUNKS), 0.0)])
def test_a_program_without_the_counters_reads_none_and_a_quiet_one_zero(
        monkeypatch, held, want):
    """What the parent commit gives (no ``chunks_overlapped``): the line
    leaves the metric out.  Both there and no chunk yet: 0.0, never a null."""
    bare = obs.Telemetry(enabled=True)
    for name in held:
        bare.counter(name)
    monkeypatch.setattr(loop_cells, "_telemetry", lambda: bare)
    value = _read()
    assert value == want and (want is None or isinstance(value, float))


def test_it_is_in_benchmark_json_for_the_two_open_loop_cells():
    reg = Registry(ROOT)
    entry = next(m for m in reg.bench["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "serving scheduler",
        "moves": "itl_p95_ms",
        "workloads": ["tfbase_lm_chat", "trinity_open_mixedlen"]}
    for cell in entry["workloads"]:
        assert "itl_p95_ms" in {e["name"]
                                for e in reg.metrics("end_to_end", cell)}
    assert contract.violations(ROOT) == []


def test_a_traced_serving_line_carries_it(toy_root):  # noqa: F811
    out = run.run_cell("tfbase_lm_chat", 13, 1.5, 1, fluid.CPUPlace(),
                       root=toy_root)
    assert out["correct"] is True
    assert out["metrics"][NAME]["unit"] == "%"
    assert 0.0 < out["metrics"][NAME]["value"] <= 100.0
