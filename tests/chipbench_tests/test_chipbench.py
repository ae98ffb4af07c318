"""The chipbench harness on the CPU at toy widths: both drivers with the
place passed in (``run.main`` is what refuses a CPU), the data-driven look-up,
the traffic generator, the trace reduction on a recorded chip trace, the FLOP
count against a hand count, and BENCHMARK.json against the contract's limits.
No test needs a chip."""
import json
import os
import re
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import paddle_tpu as fluid  # noqa: E402
from chipbench import flops, peaks, run, trace_reduce, traffic  # noqa: E402
from chipbench.registry import Registry  # noqa: E402

TOY = dict(n_layer=2, n_head=2, d_model=32, d_inner=64, vocab=64)
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _edit(root, rel, **changes):
    path = os.path.join(root, rel)
    with open(path) as f:
        data = json.load(f)
    data.update(changes)
    with open(path, "w") as f:
        json.dump(data, f)


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """A checkout in miniature: BENCHMARK.json and a copy of chipbench/ whose
    configuration and traffic files are cut to toy sizes, plus one cell, one
    configuration and one per-layer metric ADDED as new files only."""
    root = str(tmp_path_factory.mktemp("toy_checkout"))
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    _edit(root, "chipbench/configs/transformer_base_train.json", **TOY,
          first_loss_rtol=0.15)
    _edit(root, "chipbench/traffic/b64_s256.json", batch=4, seq=16,
          warmup_steps=2, sync_every=4, trace_after_s=0.1, trace_steps=4)
    _edit(root, "chipbench/configs/transformer_base_lm.json", **TOY, slots=4,
          max_seq_len=128, page=16, chunk=32, buckets=[16, 32, 128])
    _edit(root, "chipbench/traffic/chat.json", rate_rps=20.0, max_prompt=80,
          prompt_len={"dist": "lognormal", "median": 20, "sigma": 0.9,
                      "min": 4, "max": 60},
          output_len={"dist": "lognormal", "median": 6, "sigma": 0.7,
                      "min": 2, "max": 12},
          shared_prefix={"share": 0.5, "count": 2, "tokens": 16},
          trace_s=0.3, drain_limit_s=30)
    # what a later PR does: new files, new entries, no edit of the harness
    with open(os.path.join(root, "chipbench/configs/toy_lm.json"), "w") as f:
        with open(os.path.join(root, "chipbench/configs/transformer_base_lm.json")) as g:
            json.dump(dict(json.load(g), slots=2), f)
    shutil.copy(os.path.join(root, "chipbench/configs/transformer_base_lm.reference.py"),
                os.path.join(root, "chipbench/configs/toy_lm.reference.py"))
    with open(os.path.join(root, "chipbench/traffic/trickle.json"), "w") as f:
        with open(os.path.join(root, "chipbench/traffic/chat.json")) as g:
            json.dump(dict(json.load(g), rate_rps=8.0, arrivals="uniform"), f)
    with open(os.path.join(root, "chipbench/layer_metrics/completed_share_pct.py"), "w") as f:
        f.write("def read(observed):\n"
                "    return 100.0 * observed['completed'] / observed['attempted']\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy_lm", "source": "test", "reduced": [],
                             "file": "chipbench/configs/toy_lm.json", "why": "test"})
    bench["workloads"].append({"name": "toy_lm_trickle", "config": "toy_lm",
                               "traffic": "trickle", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("serve_tokens_per_s", "itl_p95_ms"):
            m["workloads"].append("toy_lm_trickle")
    bench["per_layer"].append({
        "name": "completed_share_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "serving front door",
        "moves": "serve_tokens_per_s", "workloads": ["toy_lm_trickle"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def _check_line(out, registry, cell, table):
    assert RESULT_KEYS <= set(out)
    assert json.loads(json.dumps(out)) == out          # one JSON object
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(out["device"])
    units = {m["name"]: m["unit"] for m in registry.metrics(table, cell)}
    for name, m in out["metrics"].items():
        assert m["unit"] == units[name] and np.isfinite(m["value"]), name
    return set(out["metrics"])


@pytest.mark.parametrize("trace", [0, 1])
def test_train_driver_at_toy_widths(toy_root, trace):
    out = run.run_cell("tfbase_train_s256", 2 ** 31 + 5, 1.0, trace,
                       fluid.CPUPlace(), root=toy_root)
    names = _check_line(out, Registry(toy_root), "tfbase_train_s256",
                        "per_layer" if trace else "end_to_end")
    if trace:
        # the host-side readers report; the device-trace readers find no TPU
        # plane in a CPU trace and return nothing, so those metrics are left out
        assert {"dispatch_ms.train", "feed_wait_ms",
                "compiles_in_window.train"} <= names
        assert "mfu_pct" not in names and "busy_s" not in out["device"]
    else:
        assert names == {"train_items_per_s", "setup_s"}
        assert out["metrics"]["train_items_per_s"]["value"] > 0


@pytest.mark.parametrize("trace", [0, 1])
def test_serve_driver_at_toy_widths(toy_root, trace):
    out = run.run_cell("tfbase_lm_chat", 7, 1.5, trace, fluid.CPUPlace(),
                       root=toy_root)
    names = _check_line(out, Registry(toy_root), "tfbase_lm_chat",
                        "per_layer" if trace else "end_to_end")
    assert out["attempted"] == 30                       # 20 req/s x 1.5 s
    if trace:
        assert {"generator_lag_p95_ms", "ttft_p95_ms.serve", "ttft_mean_ms.serve",
                "queue_wait_p95_ms", "decode_step_ms", "prefix_hit_pct"} <= names
    else:
        assert names == {"serve_tokens_per_s", "itl_p95_ms", "setup_s"}


def test_new_cell_config_and_metric_are_found_as_new_files(toy_root):
    out = run.run_cell("toy_lm_trickle", 3, 1.0, 1, fluid.CPUPlace(),
                       root=toy_root)
    assert out["attempted"] == 8 and out["correct"]
    assert out["metrics"]["completed_share_pct"] == {"value": 100.0, "unit": "%"}
    assert "prefix_hit_pct" not in out["metrics"]       # not this cell's


def test_token_check_sees_a_wrong_token_from_the_decode_loop(toy_root):
    import jax

    reg = Registry(toy_root)
    cfg = reg.config("transformer_base_lm")
    model = reg.module("models", cfg["model"])
    reference = reg.reference("transformer_base_lm")
    params, _ = model.make_params(cfg, 11)
    fn = jax.jit(reference.next_token_logits, static_argnums=3)
    seq = np.zeros(cfg["max_seq_len"], np.int32)
    seq[:9] = np.arange(1, 10)
    for at in range(9, 15):                             # greedy, by the reference
        seq[at] = int(np.argmax(fn(params, seq, at, cfg["n_head"])))
    prompt, served = seq[:9].copy(), seq[9:15].copy()
    assert model.token_gaps(cfg, params, [(prompt, served)], reference) == [0.0] * 3
    for k in (0, 3, 5):                                 # first, middle, last
        wrong = served.copy()
        wrong[k] = (wrong[k] + 1) % cfg["vocab"]
        gaps = model.token_gaps(cfg, params, [(prompt, wrong)], reference)
        assert max(gaps) > model.TIE_TOL, (k, gaps)


def test_main_refuses_a_cpu(capsys):
    assert run.main(["--workload", "tfbase_train_s256", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 1
    lines = capsys.readouterr().out.strip().splitlines()
    assert "platform=cpu" in lines[0] and not lines[-1].startswith("{")


def test_traffic_is_a_function_of_the_seed_alone():
    mix = Registry(ROOT).traffic("chat")
    a = traffic.arrivals(mix, 30, 2 ** 31 + 9)
    assert np.array_equal(a, traffic.arrivals(mix, 30, 2 ** 31 + 9))
    assert len(a) == round(mix["rate_rps"] * 30) and 0 < a[0] and a[-1] < 30
    assert np.all(np.diff(a) > 0)
    r1 = traffic.requests(mix, len(a), 5, 30000)
    r2 = traffic.requests(mix, len(a), 5, 30000)
    assert all(np.array_equal(p, q) and n == m
               for (p, n), (q, m) in zip(r1, r2))
    # another seed: the same gaps and the same table of requests, in another
    # order, with other tokens
    b = traffic.arrivals(mix, 30, 6)
    assert not np.array_equal(a, b)
    gaps = lambda due: np.sort(np.diff(np.concatenate([[0.0], due])))  # noqa: E731
    np.testing.assert_allclose(gaps(a), gaps(b), rtol=1e-9)
    r3 = traffic.requests(mix, len(a), 6, 30000)
    shape = lambda rs: [(len(p), n) for p, n in rs]  # noqa: E731
    assert sorted(shape(r1)) == sorted(shape(r3)) and shape(r1) != shape(r3)
    assert not any(np.array_equal(p, q) for (p, _), (q, _) in zip(r1, r3))
    lens = np.array([len(p) for p, _ in r1])
    outs = np.array([n for _, n in r1])
    assert 16 <= lens.min() and lens.max() <= mix["max_prompt"]
    assert 8 <= outs.min() and outs.max() <= 384
    assert 80 <= np.median(outs) <= 110
    # half the requests open with one of the 4 system prompts
    heads = {}
    for p, _ in r1:
        heads.setdefault(tuple(p[:192]), []).append(1)
    shared = sorted(len(v) for v in heads.values() if len(v) > 1)
    assert len(shared) == 4 and sum(shared) == round(0.5 * len(a))


def test_trace_reduce_on_a_recorded_chip_trace():
    trace = trace_reduce.load_json(os.path.join(
        ROOT, "chipbench", "testdata", "train_trace_small.json.gz"))
    assert trace_reduce.usable(trace) and trace_reduce.device_planes(trace)
    busy, window = trace_reduce.busy_and_window_s(trace)
    assert 0 < busy <= window
    lo, hi = trace_reduce.window(trace)
    ops = trace_reduce.line_events(trace, trace_reduce.device_planes(trace)[0],
                                   trace_reduce.OPS_LINE)
    # self times partition the busy time: enclosing events give up their bodies
    own = trace_reduce.self_times(ops, lo, hi)
    assert abs(sum(own.values()) - busy) <= 0.02 * busy
    top = trace_reduce.top_device_ops(trace, 5)
    assert len(top) == 5 and top[0][1] >= top[-1][1] > 0
    gaps = trace_reduce.idle_gaps(trace)
    assert sum(s for _, s in gaps) <= (window - busy) * 1.001


def test_trace_reduce_arithmetic_on_a_hand_made_trace():
    ev = lambda n, s, d: [n, s, d]  # noqa: E731
    trace = {"planes": {
        "/device:TPU:0": {
            "XLA Ops": [ev("while", 0, 100), ev("a", 10, 20), ev("b", 40, 50),
                        ev("c", 200, 100)],
            "XLA Modules": [ev("jit_step", 0, 300)]},
        "/host:CPU": {"main": [ev("chipbench.window", 0, 400),
                               ev("chipbench.readback", 90, 120),
                               ev("chipbench.feed", 300, 10)]}}}
    assert trace_reduce.busy_and_window_s(trace) == (200e-9, 400e-9)
    own = trace_reduce.self_times(trace["planes"]["/device:TPU:0"]["XLA Ops"])
    assert own == {"while": 30e-9, "a": 20e-9, "b": 50e-9, "c": 100e-9}
    assert trace_reduce.idle_gaps(trace, min_gap_ns=1) == [
        ["readback", 100e-9], ["feed", 100e-9]] or \
        trace_reduce.idle_gaps(trace, min_gap_ns=1) == [
        ["feed", 100e-9], ["readback", 100e-9]]
    assert [e[0] for e in trace_reduce.module_runs(trace)] == ["jit_step"]
    assert not trace_reduce.usable({"planes": {"/host:CPU": {}}})


def test_flops_against_the_hand_count():
    cfg = Registry(ROOT).config("transformer_base_train")
    p = flops.transformer_matmul_params(cfg)
    # encoder 6 x (4 x 512^2 + 2 x 512 x 2048), decoder 6 x (8 x 512^2 + ...),
    # head 512 x 30000: 59.4 M parameters that multiply a token
    assert p == {"encoder": 18874368, "decoder": 25165824, "head": 15360000}
    step = flops.transformer_train_step(cfg, 64, 256)
    assert step["matmul"] == 6.0 * 16384 * 59400192
    # 12 full and 6 causal (half) attentions, forward + backward = 3 x forward
    assert step["attention"] == 15 * 3 * 4.0 * 64 * 256 * 256 * 512
    assert abs(step["total"] / 6.3e12 - 1) < 0.02       # the issue's hand count
    # the same tokens at seq 2048: only the attention term grows, 8 x
    long = flops.transformer_train_step(cfg, 8, 2048)
    assert long["matmul"] == step["matmul"]
    assert long["attention"] == 8 * step["attention"]
    assert peaks.peak("TPU v5 lite", "bf16_flops") == 197e12
    with pytest.raises(KeyError):
        peaks.peak("cpu", "bf16_flops")


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_is_within_the_contract():
    reg = Registry(ROOT)
    b = reg.bench
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    names = [e["name"] for t in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in b[t]]
    assert all(NAME.match(n) for n in names)
    for t in ("configs", "workloads"):
        assert len({e["name"] for e in b[t]}) == len(b[t])
    metrics = b["end_to_end"] + b["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in b["workloads"]}
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        # its reader is a file of its own, found by name
        assert callable(reg.module("layer_metrics", m["name"]).read)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["traffic"]) and reg.traffic(w["traffic"])
        cell = w["name"]
        own = {m["name"] for m in reg.metrics("end_to_end", cell)}
        assert "setup_s" in own and len(own) >= 2
        assert reg.metrics("per_layer", cell)
        # a per-layer metric moves an end-to-end metric its cells report
        for m in reg.metrics("per_layer", cell):
            assert m["moves"] in own, (cell, m["name"])
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        cfg = reg.config(c["name"])
        # the vocabulary is the repo's 30000 against the paper's 37000, and
        # says so; widths as published (Vaswani et al. 2017, table 3, "base")
        assert cfg["reduced"] == c["reduced"] == ["vocab"]
        assert set(cfg["reduced_why"]) == {"vocab"}
        assert (cfg["n_layer"], cfg["n_head"], cfg["d_model"],
                cfg["d_inner"]) == (6, 8, 512, 2048)
        assert reg.reference(c["name"])
        assert any(w["config"] == c["name"] for w in b["workloads"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
