"""The chipbench harness on the CPU at toy widths: both drivers with the
place passed in (``run.main`` is what refuses a CPU), the data-driven look-up,
the traffic generator, the trace reduction on a recorded chip trace, the FLOP
count against a hand count, and ``chipbench/contract.py`` on the repo's root, on
a toy checkout that ADDS a configuration of another family by new files alone,
and on copies of it that each break one rule.  No test needs a chip."""
import hashlib
import json
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import paddle_tpu as fluid  # noqa: E402
from chipbench import contract, flops, peaks, run, trace_reduce, traffic  # noqa: E402
from chipbench.registry import Registry  # noqa: E402

TOY = dict(n_layer=2, n_head=2, d_model=32, d_inner=64, vocab=64)
# a toy is not the model: its cut of the two real files cuts their published
# blocks with their widths (the pin by name is on the repo's root, below)
TOY_PUBLISHED = dict(TOY, vocab=37000)
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
SERVE_PHASES = {"sched_iteration_ms", "sched_host_ms", "decode_wait_ms",
                "prefill_chunk_ms", "chunk_iteration_share_pct", "admit_ms",
                "setup_warmup_s"}
FIRST_CELLS = ("tfbase_train_s256", "tfbase_lm_chat", "tfbase_train_s2048")
FAMILY = "chipbench/configs/toy_family.json"
# a family of its own: other key names for its sizes, a model file that builds
# the system from them (here by handing them to the LM's builders)
TOY_FAMILY_MODEL = '''
from chipbench.models import transformer_lm as lm

PAGED_RTOL, TIE_TOL = lm.PAGED_RTOL, lm.TIE_TOL


def _sizes(cfg):
    return dict(cfg, n_layer=cfg["layers"], n_head=cfg["heads"],
                d_model=cfg["hidden"], d_inner=cfg["ffn"])


def make_params(cfg, seed):
    return lm.make_params(_sizes(cfg), seed)


def build_engine(cfg, params, meta, max_new_tokens):
    return lm.build_engine(_sizes(cfg), params, meta, max_new_tokens)


def paged_kernel_errors(cfg, seed, reference):
    return lm.paged_kernel_errors(_sizes(cfg), seed, reference)


def token_gaps(cfg, params, samples, reference):
    return lm.token_gaps(_sizes(cfg), params, samples, reference)
'''
TOY_FAMILY = {
    "source": "test", "driver": "serve", "model": "toy_family",
    "layers": 2, "heads": 2, "hidden": 32, "ffn": 64, "vocab": 64,
    "slots": 2, "max_seq_len": 128, "page": 16, "num_pages": None,
    "kv_dtype": "bfloat16", "chunk": 32, "buckets": [16, 32, 128],
    "prefix_cache": True, "queue_capacity": 4096,
    "published": {"layers": 4, "heads": 2, "hidden": 32, "ffn": 64, "vocab": 64},
    "reduced": ["layers"],
    "reduced_why": {"layers": "2 of the 4: one period of its pattern"},
    "assumed": {"slots": "the source fixes no number of sequences"},
}


DROP = object()


def _edit(root, rel, **changes):
    """Set keys of the JSON file ``rel``; the value ``DROP`` takes a key out."""
    path = os.path.join(root, rel)
    with open(path) as f:
        data = json.load(f)
    data.update(changes)
    with open(path, "w") as f:
        json.dump({k: v for k, v in data.items() if v is not DROP}, f)


def _edit_bench(root, change):
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    change(bench)
    with open(path, "w") as f:
        json.dump(bench, f)


def _entry(bench, table, name):
    return next(e for e in bench[table] if e["name"] == name)


def _keep_cells(bench, keep):
    """Cut ``bench`` to the cells ``keep`` and to the metrics and the
    configurations they use."""
    bench["workloads"] = [w for w in bench["workloads"] if w["name"] in keep]
    for table in ("end_to_end", "per_layer"):
        for m in bench[table]:
            if "workloads" in m:
                m["workloads"] = [c for c in m["workloads"] if c in keep]
        bench[table] = [m for m in bench[table] if m.get("workloads", True)]
    used = {w["config"] for w in bench["workloads"]}
    bench["configs"] = [c for c in bench["configs"] if c["name"] in used]


def _metrics_of(bench, cell):
    """The metric entries that list ``cell`` by name."""
    return [m for m in bench["end_to_end"] + bench["per_layer"]
            if cell in m.get("workloads", ())]


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """A checkout in miniature: the benchmark's first three cells (what a
    later PR has added stays on the repo's root, at its own sizes) over a copy
    of chipbench/ whose configuration and traffic files are cut to toy sizes,
    plus what later PRs ADD as new files and entries only: a cell, a
    configuration and a per-layer metric; and a configuration of ANOTHER
    FAMILY with its model file, its reference and a serving cell that lists
    itself under every metric ``tfbase_lm_chat`` reports."""
    root = str(tmp_path_factory.mktemp("toy_checkout"))
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    _edit(root, "chipbench/configs/transformer_base_train.json", **TOY,
          published=TOY_PUBLISHED, first_loss_rtol=0.15)
    _edit(root, "chipbench/traffic/b64_s256.json", batch=4, seq=16,
          warmup_steps=2, sync_every=4, trace_after_s=0.1, trace_steps=4)
    _edit(root, "chipbench/configs/transformer_base_lm.json", **TOY,
          published=TOY_PUBLISHED, slots=4, max_seq_len=128, page=16, chunk=32,
          buckets=[16, 32, 128])
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
    with open(os.path.join(root, "chipbench/models/toy_family.py"), "w") as f:
        f.write(TOY_FAMILY_MODEL)
    with open(os.path.join(root, FAMILY), "w") as f:
        json.dump(TOY_FAMILY, f)
    shutil.copy(os.path.join(root, "chipbench/configs/transformer_base_lm.reference.py"),
                os.path.join(root, "chipbench/configs/toy_family.reference.py"))

    def add(bench):
        _keep_cells(bench, FIRST_CELLS)
        bench["configs"] += [
            {"name": "toy_lm", "source": "test", "reduced": ["vocab"],
             "file": "chipbench/configs/toy_lm.json", "why": "test"},
            {"name": "toy_family", "source": "test", "reduced": ["layers"],
             "file": FAMILY, "why": "test"}]
        bench["workloads"] += [
            {"name": "toy_lm_trickle", "config": "toy_lm",
             "traffic": "trickle", "chips": 1, "why": "test"},
            {"name": "toy_family_trickle", "config": "toy_family",
             "traffic": "trickle", "chips": 1, "why": "test"}]
        for m in _metrics_of(bench, "tfbase_lm_chat"):
            m["workloads"].append("toy_family_trickle")
        for name in ("serve_tokens_per_s", "itl_p95_ms"):
            _entry(bench, "end_to_end", name)["workloads"].append("toy_lm_trickle")
        bench["per_layer"].append({
            "name": "completed_share_pct", "unit": "%", "better": "higher",
            "source": "program_counter", "layer": "serving front door",
            "moves": "serve_tokens_per_s", "workloads": ["toy_lm_trickle"]})

    _edit_bench(root, add)
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


@pytest.mark.parametrize("trace", [0, 1])
def test_a_configuration_of_a_new_family_is_served_from_new_files_alone(
        toy_root, trace):
    out = run.run_cell("toy_family_trickle", 2 ** 31 + 11, 1.0, trace,
                       fluid.CPUPlace(), root=toy_root)
    names = _check_line(out, Registry(toy_root), "toy_family_trickle",
                        "per_layer" if trace else "end_to_end")
    assert out["attempted"] == 8
    if trace:
        assert SERVE_PHASES | {"decode_step_ms", "prefix_hit_pct"} <= names
        assert "completed_share_pct" not in names       # not this cell's
    else:
        assert names == {"serve_tokens_per_s", "itl_p95_ms", "setup_s"}


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


def _shape(reqs):
    return [(len(p), n) for p, n in reqs]


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
    assert sorted(_shape(r1)) == sorted(_shape(r3)) and _shape(r1) != _shape(r3)
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


def _digest(array, dtype):
    return hashlib.sha256(np.asarray(array, dtype).tobytes()).hexdigest()[:16]


def _an_order_seed_fixes_the_schedule_and_leaves_the_tokens_to_the_seed():
    mix = dict(Registry(ROOT).traffic("chat"), order_seed=3)
    a = traffic.arrivals(mix, 30, 5)
    assert np.array_equal(a, traffic.arrivals(mix, 30, 2 ** 31 + 9))
    r1 = traffic.requests(mix, len(a), 5, 30000)
    r2 = traffic.requests(mix, len(a), 2 ** 31 + 9, 30000)
    assert _shape(r1) == _shape(r2)
    assert not any(np.array_equal(p, q) for (p, _), (q, _) in zip(r1, r2))
    # another order_seed is another order of the same gaps and rows
    other = dict(mix, order_seed=4)
    b = traffic.arrivals(other, 30, 5)
    assert not np.array_equal(a, b)
    np.testing.assert_allclose(np.sort(np.diff(a, prepend=0.0)),
                               np.sort(np.diff(b, prepend=0.0)), rtol=1e-9)
    r3 = traffic.requests(other, len(a), 5, 30000)
    assert sorted(_shape(r1)) == sorted(_shape(r3)) and _shape(r1) != _shape(r3)


def _a_mix_without_the_key_is_the_parents_byte_for_byte():
    """Digests taken on the commit before ``order_seed`` existed (PR 46)."""
    reg = Registry(ROOT)
    for name in ("chat", "standing_longctx", "standing_midctx",
                 "standing_mixedctx", "standing_reasoning128"):
        assert "order_seed" not in reg.traffic(name), name
    mix = reg.traffic("chat")
    a = traffic.arrivals(mix, 30, 2 ** 31 + 9)
    r = traffic.requests(mix, len(a), 2 ** 31 + 9, 30000)
    assert len(a) == 660
    assert _digest(a, np.float64) == "760eaf9eda747a30"
    assert _digest(_shape(r), np.int64) == "1ce0cf6b0a6bd011"
    assert _digest(np.concatenate([p for p, _ in r]),
                   np.int32) == "ec8fc8fc65d9f9f6"


def _open_mixedlen_times_one_schedule_with_its_longest_prompt_early():
    reg = Registry(ROOT)
    mix = reg.traffic("open_mixedlen")
    seconds = reg.bench["run_seconds"]
    assert isinstance(mix["order_seed"], int) and seconds == 40
    assert mix["rate_rps"] == 6.4 == 0.8 * mix["knee_rps"]
    due = traffic.arrivals(mix, seconds, 11)
    reqs = traffic.requests(mix, len(due), 11, 25024)
    assert len(due) == 256
    lens = np.array([len(p) for p, _ in reqs])
    # what ``correct`` checks: the first of the longest prompts (its ring
    # wraps), due early enough to be served inside the window
    assert lens.max() == mix["max_prompt"] == 16384
    assert due[int(np.argmax(lens))] <= 0.75 * seconds
    # and the schedule is the cell's whatever the run's seed
    assert np.array_equal(due, traffic.arrivals(mix, seconds, 2 ** 31 + 12))
    assert _shape(reqs) == _shape(
        traffic.requests(mix, len(due), 2 ** 31 + 12, 25024))


@pytest.mark.parametrize("case", [
    _an_order_seed_fixes_the_schedule_and_leaves_the_tokens_to_the_seed,
    _a_mix_without_the_key_is_the_parents_byte_for_byte,
    _open_mixedlen_times_one_schedule_with_its_longest_prompt_early,
], ids=lambda f: f.__name__.strip("_"))
def test_the_order_of_a_schedule_is_the_mixs_where_it_says_so(case):
    case()


def test_sweep_reads_candidate_orders_through_one_engine(toy_root):
    from chipbench import sweep

    lines = []
    rows = sweep.sweep(Registry(toy_root), "tfbase_lm_chat", [20.0], 1.0,
                       [7, 8], log=lines.append, order_seeds=[1, 2])
    assert [(r["order_seed"], r["seed"]) for r in rows] == [
        (1, 7), (1, 8), (2, 7), (2, 8)]
    assert [json.loads(x) for x in lines] == rows
    for r in rows:
        assert r["attempted"] == 20 == r["completed"] and r["failed"] == 0
        assert 0 < r["chunk_iteration_share_pct"] <= 100
    # without the option the mix decides: chat has no key, the seed orders
    rows = sweep.sweep(Registry(toy_root), "tfbase_lm_chat", [20.0], 0.5, [7],
                       log=lines.append)
    assert rows[0]["order_seed"] is None and rows[0]["attempted"] == 10


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


# Vaswani et al. 2017, table 3, row "base", and section 5.1's EN-DE vocabulary:
# the two configurations are held to these through their own files' published
# blocks, which the contract compares with the sizes as run
PUBLISHED_BASE = {"n_layer": 6, "n_head": 8, "d_model": 512, "d_inner": 2048,
                  "vocab": 37000}
BASE_REDUCED = ["vocab"]


def test_benchmark_json_is_within_the_contract(toy_root):
    assert contract.violations(ROOT) == []
    assert contract.violations(toy_root) == []
    reg = Registry(ROOT)
    for name in ("transformer_base_train", "transformer_base_lm"):
        cfg = reg.config(name)
        assert cfg["published"] == PUBLISHED_BASE
        assert cfg["reduced"] == BASE_REDUCED and cfg["vocab"] == 30000


def _more_cells(bench, n, root):
    """``n`` cells more, each like ``toy_lm_trickle`` under a mix of its own."""
    for i in range(n):
        shutil.copy(os.path.join(root, "chipbench/traffic/trickle.json"),
                    os.path.join(root, "chipbench/traffic/trickle_%d.json" % i))
        cell = "toy_lm_trickle_%d" % i
        bench["workloads"].append(dict(_entry(bench, "workloads", "toy_lm_trickle"),
                                       name=cell, traffic="trickle_%d" % i))
        for m in _metrics_of(bench, "toy_lm_trickle"):
            m["workloads"].append(cell)


def _rename_metric(bench, root, old, new):
    _entry(bench, "per_layer", old)["name"] = new
    os.rename(os.path.join(root, "chipbench/layer_metrics", old + ".py"),
              os.path.join(root, "chipbench/layer_metrics", new + ".py"))


def _drop_cell(bench, cell):
    bench["workloads"].remove(_entry(bench, "workloads", cell))
    for m in _metrics_of(bench, cell):
        m["workloads"].remove(cell)


def _json(rel, **changes):
    return lambda root: _edit(root, rel, **changes)


def _bench(change):
    """``change(bench, root)`` on the copy's BENCHMARK.json."""
    return lambda root: _edit_bench(root, lambda b: change(b, root))


def _set(table, name, **changes):
    return _bench(lambda b, _: _entry(b, table, name).update(changes))


# one rule broken in a copy of the toy checkout -> the one violation it gives
BREAKS = {
    "a width off published and not in reduced": (
        _json(FAMILY, hidden=48),
        "toy_family.json: hidden is 48, published 32, and is not in reduced"),
    "a Transformer-base width off its published block": (
        _json("chipbench/configs/transformer_base_train.json", d_inner=128),
        "transformer_base_train.json: d_inner is 128, published 64"),
    "a reduced name with no reduced_why": (
        _json(FAMILY, reduced_why={}),
        "reduced names layers, reduced_why has no line for it"),
    "a reduced_why for a key that is not reduced": (
        _json(FAMILY, reduced_why=dict(TOY_FAMILY["reduced_why"], ffn="x")),
        "reduced_why has ffn, which reduced does not name"),
    "a reduced key that is as published": (
        _json(FAMILY, layers=4), "reduced names layers, which is as published"),
    "the file's reduced is not its entry's": (
        _json(FAMILY, reduced=[]), "reduced [], its BENCHMARK.json entry has"),
    "a key both assumed and published": (
        _json(FAMILY, assumed={"hidden": "guessed"}),
        "hidden is both assumed and published"),
    "no published block": (
        _json(FAMILY, published=DROP), "toy_family.json: no published block"),
    "a second 4-chip cell among fewer than eight": (
        _bench(lambda b, _: [w.update(chips=4) for w in b["workloads"][:2]]),
        "2 of 5 cells ask for 4 chips"),
    "a 25th cell": (
        _bench(lambda b, root: _more_cells(b, 20, root)),
        "workloads: 25 entries, not 1 to 24"),
    "a per-layer metric with no list": (
        _bench(lambda b, _: _entry(b, "per_layer", "sched_host_ms").pop("workloads")),
        "per_layer sched_host_ms: lacks ['workloads']"),
    "a per-layer metric with an empty list": (
        _set("per_layer", "prefix_hit_pct", workloads=[]),
        "per_layer prefix_hit_pct: workloads is []"),
    "a train cell under a phase that moves itl_p95_ms": (
        _bench(lambda b, _: _entry(b, "per_layer", "sched_iteration_ms")[
            "workloads"].append("tfbase_train_s256")),
        "sched_iteration_ms: lists tfbase_train_s256, which does not report "
        "itl_p95_ms"),
    "a metric that lists no cell of the benchmark": (
        _bench(lambda b, _: _entry(b, "per_layer", "admit_ms")[
            "workloads"].append("tfbase_lm_longctx")),
        "admit_ms: lists ['tfbase_lm_longctx'], no cells of the benchmark"),
    "a 65-letter name": (
        _bench(lambda b, root: _rename_metric(b, root, "completed_share_pct",
                                              "c" * 65)),
        "per_layer: '%s' is not a name" % ("c" * 65)),
    "a per-layer metric with no reader file": (
        _bench(lambda b, _: b["per_layer"].append(dict(
            _entry(b, "per_layer", "admit_ms"), name="evict_ms"))),
        "per_layer evict_ms: no reader layer_metrics/evict_ms.py loads"),
    "a key the contract does not have": (
        _set("per_layer", "admit_ms", why="x"),
        "per_layer admit_ms: keys ['why'] are not the contract's"),
    "a bound over 0.1": (
        _set("end_to_end", "itl_p95_ms", bound=0.2),
        "end_to_end itl_p95_ms: bound 0.2 is not within 0.01 to 0.1"),
    "a why of 201 characters": (
        _set("workloads", "tfbase_lm_chat", why="y" * 201),
        "workloads tfbase_lm_chat: why is not 1 to 200 characters"),
    "a cell whose traffic file is missing": (
        lambda root: os.remove(os.path.join(root, "chipbench/traffic/chat.json")),
        "workloads tfbase_lm_chat: no file traffic/chat.json"),
    "a configuration with no cell": (
        _bench(lambda b, _: _drop_cell(b, "toy_family_trickle")),
        "configs toy_family: no cell uses it"),
    "a missing reference": (
        lambda root: os.remove(os.path.join(
            root, "chipbench/configs/toy_family.reference.py")),
        "toy_family.json: no plain reference"),
    "a driver that names no file": (
        _json(FAMILY, driver="serve_sessions"),
        "toy_family.json: driver 'serve_sessions' names no file"),
    "a model that names no file": (
        _json(FAMILY, model="hybrid_lm"),
        "toy_family.json: model 'hybrid_lm' names no file"),
    "a key beside the contract's seven": (
        _bench(lambda b, _: b.update(notes="x")), "BENCHMARK.json: top-level keys"),
    "run_seconds over what 24 cells leave room for": (
        _bench(lambda b, _: b.update(run_seconds=52)),
        "run_seconds: 52 is not a whole number from 1 to 51"),
}


@pytest.fixture
def toy_copy(toy_root, tmp_path):
    """A copy of the toy checkout that a test may break."""
    root = str(tmp_path / "broken")
    shutil.copytree(toy_root, root,
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    return root


@pytest.mark.parametrize("case", sorted(BREAKS))
def test_contract_names_the_one_rule_that_is_broken(toy_copy, case):
    change, want = BREAKS[case]
    change(toy_copy)
    found = contract.violations(toy_copy)
    assert len(found) == 1 and want in found[0], found


def test_contract_main_prints_the_violations_and_exits_1(toy_root, toy_copy, capsys):
    assert contract.main([toy_root]) == 0
    assert capsys.readouterr().out == "0 violations\n"
    _edit(toy_copy, FAMILY, hidden=48, driver="serve_sessions")
    assert contract.main([toy_copy]) == 1
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == 3 and lines[-1] == "2 violations"
