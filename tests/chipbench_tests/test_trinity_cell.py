"""The open-loop driver for a cache in page GROUPS and a share of routed experts
(``drivers/serve_open_moe.py``), its model builder (``models/afmoe.py``), the
plain reference and the six per-layer readers of ``trinity_open_mixedlen``
through ``run.run_cell`` on a toy checkout at toy widths on the CPU, at
``--trace 0`` and ``1``; controls of the cell's limits (an 8-bit K/V row,
bfloat16 router scores, rotary in the full layer each come out not correct,
by a limit that names them; a window one short or long reads past its limit
beside the sound reading); the contract on that
checkout and on the repo; the byte and operation counts on a hand-counted toy;
the readers on hand-made observations; and the configuration's own file
against the catalog's facts.  No test needs a chip."""
import ast
import json
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import paddle_tpu as fluid  # noqa: E402
from chipbench import contract, kanana_decode, run  # noqa: E402
from chipbench import trinity_serve as T  # noqa: E402
from chipbench.registry import Registry  # noqa: E402

CELL, NAME, TRAFFIC = ("trinity_open_mixedlen", "trinity_large_preview",
                       "open_mixedlen")
CONFIG = "chipbench/configs/trinity_large_preview.json"
NEW_METRICS = ("chunk_mfu_pct.trinity", "decode_hbm_mfu_pct.trinity",
               "moe_expert_roofline_pct.trinity",
               "attn_walk_roofline_pct.trinity", "experts_touched_pct.trinity",
               "window_pages_reused_per_s")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# toy sizes in the family's own key names; a toy is not the model, so its
# published block is cut with it
KINDS = ["sliding_attention", "sliding_attention", "full_attention",
         "sliding_attention", "sliding_attention"]
TOY = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
           vocab_size=96, num_attention_heads=4, num_key_value_heads=2,
           head_dim=16, num_experts=4, num_experts_per_tok=2,
           sliding_window=24, max_position_embeddings=2048)
TOY_SIZES = dict(router_experts=16, experts_held=[0, 4], vocab=96,
                 vocab_slice=[0, 96], weights_dtype="float32",
                 kv_dtype="float32", slots=4, max_seq_len=160, page=8,
                 chunk=16, buckets=[8, 16, 128],
                 num_pages={"full": 81, "window": 25})


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("toy_trinity"))
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    path = os.path.join(root, CONFIG)
    with open(path) as f:
        cfg = json.load(f)
    published = dict(cfg["published"], **TOY)
    published.update(num_hidden_layers=8, layer_types=KINDS[2:] + KINDS,
                     num_dense_layers=2, num_experts=16, vocab_size=768)
    cfg.update(TOY, **TOY_SIZES, published=published)
    with open(path, "w") as f:
        json.dump(cfg, f)
    path = os.path.join(root, "chipbench/traffic/%s.json" % TRAFFIC)
    with open(path) as f:
        mix = json.load(f)
    mix.update(rate_rps=6.0, knee_rps=7.5, max_prompt=128, trace_s=0.3,
               checked_sequences=3,
               drain_limit_s=300,
               prompt_len={"dist": "lognormal", "median": 40, "sigma": 0.9,
                           "min": 10, "max": 128},
               output_len={"dist": "lognormal", "median": 8, "sigma": 0.5,
                           "min": 6, "max": 24})
    with open(path, "w") as f:
        json.dump(mix, f)
    return root


def test_the_repo_and_the_toy_checkout_are_within_the_contract(toy_root):
    assert contract.violations(ROOT) == []
    assert contract.violations(toy_root) == []


def _held(log):
    return ast.literal_eval(log.split("open: served state ", 1)[1].split(
        "; mechanism errors", 1)[0].replace("inf", "1e999"))


def _mechanisms(log):
    return ast.literal_eval(log.split("; mechanism errors ", 1)[1].split(
        "; checks", 1)[0].replace("inf", "1e999"))


def _model(toy_root):
    return kanana_decode.builder(Registry(toy_root).config(NAME))


@pytest.mark.parametrize("trace", [0, 1])
def test_open_groups_driver_at_toy_widths(toy_root, trace, capsys):
    out = run.run_cell(CELL, 2 ** 31 + 5, 2.0, trace, fluid.CPUPlace(),
                       root=toy_root)
    log = capsys.readouterr().out
    assert out["correct"] is True and out["failed"] == 0, log[-3000:]
    assert out["attempted"] == 12
    held = _held(log)
    # the engine's window pages had all been handed out and taken back by the
    # window's own requests before the check took them again
    assert held["window_pages_taken_before"] > 24
    assert held["window_pages_left"] == held["full_pages_left"] == 0
    reg = Registry(toy_root)
    table = "per_layer" if trace else "end_to_end"
    units = {m["name"]: m["unit"] for m in reg.metrics(table, CELL)}
    for name, m in out["metrics"].items():
        assert m["unit"] == units[name] and np.isfinite(m["value"]), name
    if not trace:
        assert set(out["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                       "setup_s"}
        assert out["metrics"]["itl_p95_ms"]["value"] > 0
        assert out["metrics"]["serve_tokens_per_s"]["value"] > 0
        return
    # no device in a CPU trace: the device readers leave their metrics out,
    # the counter and span readers report
    got = set(out["metrics"])
    assert {"experts_touched_pct.trinity", "window_pages_reused_per_s",
            "decode_step_ms", "chunk_iteration_share_pct", "chunk_program_ms",
            "prefill_chunk_ms", "sched_iteration_ms", "steps_overlapped_pct",
            "loop_unaccounted_pct", "setup_warmup_s", "ttft_p95_ms.serve",
            "ttft_mean_ms.serve", "ttft_p50_ms.serve", "queue_wait_p95_ms",
            "generator_lag_p95_ms", "admit_ms", "host_gc_s"} <= got
    assert 0 < out["metrics"]["experts_touched_pct.trinity"]["value"] <= 100
    assert out["metrics"]["window_pages_reused_per_s"]["value"] > 0
    assert not got & {"chunk_mfu_pct.trinity", "decode_hbm_mfu_pct.trinity",
                      "moe_expert_roofline_pct.trinity",
                      "attn_walk_roofline_pct.trinity"}


def test_an_eight_bit_row_comes_out_not_correct(toy_root, monkeypatch, capsys):
    """The control of ``SERVED_STATE_TOL``'s ``kv_rows``: K and V rows kept
    in 8 bits (the precision below the 16 the configuration states) fail the
    cell by the reading taken from the engine's own programs on its own
    cache; the stand-alone mechanisms, which bring their own pools, do not
    see it."""
    import jax.numpy as jnp

    from paddle_tpu.models import afmoe as A

    def eight_bit(step):
        def rounded(*args, **kwargs):
            out = step(*args, **kwargs)
            cache = {name: leaf.astype(jnp.float8_e4m3fn).astype(leaf.dtype)
                     for name, leaf in out[1].items()}
            return (out[0], cache) + tuple(out[2:])
        return rounded

    for name in ("decode_step", "prefill_chunk"):
        monkeypatch.setattr(A, name, eight_bit(getattr(A, name)))
    out = run.run_cell(CELL, 2 ** 31 + 9, 2.0, 0, fluid.CPUPlace(),
                       root=toy_root)
    assert out["correct"] is False and out["failed"] == 0
    log = capsys.readouterr().out
    assert "NOT CORRECT: the engine's own programs on its own cache" in log
    assert "NOT CORRECT: mechanisms" not in log
    assert _held(log)["kv_rows"] > 2 * _model(toy_root).SERVED_STATE_TOL[
        "kv_rows"]


def test_eight_bit_weights_in_the_engine_come_out_not_correct(
        toy_root, monkeypatch, capsys):
    """The control of ``TOKENS_AGREE``: an engine that serves weight matrices
    rounded to 8 bits (the checks keep the sound ones) serves tokens of which
    too few lie at the reference's top, and leaves deep rows the reference's
    are not; the stand-alone mechanisms, on the sound weights, hold."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import afmoe as A

    real = A.build_decode_model

    def rounded(weights, cfg, **kw):
        return real(jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
            if x.ndim >= 2 else x, weights), cfg, **kw)

    monkeypatch.setattr(A, "build_decode_model", rounded)
    out = run.run_cell(CELL, 2 ** 31 + 9, 2.0, 0, fluid.CPUPlace(),
                       root=toy_root)
    assert out["correct"] is False and out["failed"] == 0
    log = capsys.readouterr().out
    assert "NOT CORRECT: mechanisms" not in log
    assert "served tokens within" in log
    assert _held(log)["kv_rows_deep"] > 5 * _model(
        toy_root).SERVED_STATE_TOL["kv_rows_deep"]


def test_bfloat16_router_scores_come_out_not_correct(toy_root, monkeypatch,
                                                     capsys):
    """The control of ``routing_mismatch``: a router whose logits come from
    bfloat16 operands fails the cell by that limit; the walks' limits hold."""
    from paddle_tpu.parallel import moe

    model = _model(toy_root)
    real = moe.route_topk

    def coarse(x, w, bias, *, top_k, scale=1.0, **kw):
        import jax
        import jax.numpy as jnp

        experts = model._route_bf16(x, w, bias, top_k)
        weights = jnp.take_along_axis(jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), w)), experts, axis=-1)
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
        return experts.astype(jnp.int32), weights * scale

    monkeypatch.setattr(moe, "route_topk", coarse)
    out = run.run_cell(CELL, 2 ** 31 + 9, 2.0, 0, fluid.CPUPlace(),
                       root=toy_root)
    monkeypatch.setattr(moe, "route_topk", real)
    assert out["correct"] is False and out["failed"] == 0
    log = capsys.readouterr().out
    assert "NOT CORRECT: mechanisms vs reference" in log
    errs = _mechanisms(log)
    assert errs["routing_mismatch"] > model.MECHANISM_RTOL["routing_mismatch"]
    for name in ("full_decode", "window_decode", "full_prefill",
                 "window_prefill"):
        assert errs[name] <= model.MECHANISM_RTOL[name], errs


def test_the_controls_read_beside_the_sound_run_lie_past_their_limits(
        toy_root, capsys):
    """A window of W - 1 and of W + 1 and each wrong reading of a K row, read
    in every run beside the sound one, lie past the limit that the sound one
    is under."""
    out = run.run_cell(CELL, 2 ** 31 + 9, 2.0, 0, fluid.CPUPlace(),
                       root=toy_root)
    assert out["correct"] is True
    errs, model = _mechanisms(capsys.readouterr().out), _model(toy_root)
    for name in ("window_decode", "window_prefill"):
        assert errs[name] <= model.MECHANISM_RTOL[name]
        for variant in ("_short", "_long"):
            assert errs[name + variant] > 2 * model.MECHANISM_RTOL[name], errs
    for name in ("moe_decode", "moe_prefill"):
        assert errs[name] <= model.MECHANISM_RTOL[name]
        # ONE held pair dropped, the one of least weight
        assert errs[name + "_pair_dropped"] > 2 * model.MECHANISM_RTOL[name]
    for name in ("k_rows_no_qk_norm", "k_rows_no_rotary",
                 "k_rows_rotary_in_full"):
        assert errs[name] > 2 * model.DEEP_ROW_TOL, errs


def test_rotary_in_the_full_layer_comes_out_not_correct(toy_root, monkeypatch,
                                                        capsys):
    """The control of ``kv_rows_deep``: step programs whose FULL layer rotates
    q and k as the sliding layers do leave K rows in the full group that the
    reference's are not, and the cell comes out not correct by that reading
    (layer 0, a sliding layer, still holds ``kv_rows``)."""
    from paddle_tpu.models import afmoe as A

    real = A._attn_in

    def wrong(d, p, lp, layer, x, positions):
        return real(dict(d, kinds=["sliding_attention"] * len(d["kinds"])), p,
                    lp, layer, x, positions)

    monkeypatch.setattr(A, "_attn_in", wrong)
    out = run.run_cell(CELL, 2 ** 31 + 9, 2.0, 0, fluid.CPUPlace(),
                       root=toy_root)
    assert out["correct"] is False and out["failed"] == 0
    log = capsys.readouterr().out
    assert "NOT CORRECT: the engine's own programs on its own cache" in log
    held, model = _held(log), _model(toy_root)
    assert held["kv_rows_deep"] > 5 * model.SERVED_STATE_TOL["kv_rows_deep"]
    assert held["kv_rows"] <= model.SERVED_STATE_TOL["kv_rows"]


# -- the bytes and operations, hand-counted ------------------------------------

def test_bytes_and_operations_on_a_hand_counted_toy(toy_root):
    cfg = Registry(toy_root).config(NAME)       # float32 weights and rows
    attn = 64 * (2 * 64 + 2 * 32) + 64 * 64           # q, gate | k, v | o
    assert T.attention_params(cfg) == attn == 16384
    assert T.expert_params(cfg) == 3 * 64 * 32 == 6144
    resident = 5 * attn + 1 * 3 * 64 * 96 + 4 * 6144
    assert T.resident_params(cfg) == resident == 124928
    assert T.router_params(cfg) == 4 * 64 * 16
    assert T.kv_row_bytes(cfg) == 2 * 2 * 16 * 4 == 256
    counts = dict(rows=3, pairs=3 * 2 * 4, pairs_held=7, experts_touched=5,
                  full_tokens=100, window_tokens=4 * 60)
    vectors = 4 * (5 * (4 * 64 + 2 * 16) + 64)
    weights = 4 * (resident + 64 * 96 + 3 * 64) + 4 * 4 * 64 * 16 + vectors
    assert T.weight_bytes(cfg, 3, 4) == weights
    assert T.weight_bytes(cfg, 3, 0) == weights - 4 * 64 * 96
    assert T.expert_bytes(cfg, 5) == 4 * 6144 * 5
    assert T.kv_bytes(cfg, counts) == 256 * (100 + 240 + 3 * 5)
    assert T.program_bytes(cfg, counts, 4) == (
        weights + 4 * 6144 * 5 + 256 * 355)
    assert T.program_flops(cfg, counts, 4) == (
        2 * 3 * (resident + 4 * 64 * 16) + 2 * 4 * 64 * 96 + 2 * 6144 * 7
        + 4 * 4 * 16 * 340)


def test_the_published_sizes_give_the_bytes_the_configuration_states():
    cfg = Registry(ROOT).config(NAME)
    assert T.attention_params(cfg) == 62914560
    assert T.expert_params(cfg) == 28311552
    total = (T.resident_params(cfg) + T.router_params(cfg)
             + 4 * 32 * T.expert_params(cfg) + 2 * 25024 * 3072)
    assert round(total / 1e9, 2) == 4.32
    assert T.kv_row_bytes(cfg) == 4096


# -- the readers ---------------------------------------------------------------

def _counters(chunk, runs, **per_run):
    from chipbench import loop_cells

    out = {T.PROGRAMS[chunk][1]: runs}
    for key, value in per_run.items():
        out[loop_cells.labeled(T.PREFIX + T.NAMES[key], chunk=chunk)] = (
            runs * value)
    return out


def _observed(config, **more):
    from chipbench import loop_cells

    counters = dict(
        _counters(0, 10, pairs=3 * 2 * 4, pairs_held=7, experts_touched=5,
                  full_tokens=100, window_tokens=240),
        **_counters(1, 4, pairs=12 * 2 * 4, pairs_held=20, experts_touched=9,
                    full_tokens=500, window_tokens=900))
    counters[loop_cells.labeled("serving.cache.pages_released",
                                group="window")] = 30
    # between the trace's edges the programs were lighter than the window's
    traced = dict(
        _counters(0, 2, pairs=2 * 2 * 4, pairs_held=4, experts_touched=3,
                  full_tokens=60, window_tokens=120),
        **_counters(1, 1, pairs=8 * 2 * 4, pairs_held=10, experts_touched=6,
                    full_tokens=300, window_tokens=500))
    base = {"config": config, "seconds": 2.0,
            "peak": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}.get,
            "window_counters": counters, "traced_counters": traced}
    base.update(more)
    return base


def _trace():
    """A hand-made trace: two decode steps of 1 ms (per step one full walk of
    100 us, four window walks of 20 us, eight grouped products of 50 us) and
    between them one chunk program of 3 ms with the same kernels at five
    times the time."""
    ops, mods, t = [], [], 1000

    def program(name, dur, scale):
        nonlocal t
        mods.append([name, t, dur])
        at = t
        calls = [("paged_gqa_full_attention.1 custom-call f32[4,32,16]",
                  100_000 * scale)]
        calls += [("paged_gqa_window_attention.%d custom-call f32[4,32,16]"
                   % i, 20_000 * scale) for i in range(4)]
        calls += [("moe_grouped_matmul.%d custom-call f32[16,64]" % i,
                   50_000 * scale) for i in range(8)]
        for op, d in calls:
            ops.append([op, at, d])
            at += d
        t += dur + 200_000

    program("jit_decode(1)", 1_000_000, 1)
    program("jit_chunk(2)", 3_000_000, 5)
    program("jit_decode(1)", 1_000_000, 1)
    return {"planes": {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods}}}


def test_device_readers_on_a_hand_made_trace(toy_root):
    reg = Registry(toy_root)
    cfg = reg.config(NAME)
    obs = _observed(cfg, trace=_trace(), busy_s=5e-3, traced_window_s=6e-3)

    def read(name):
        return reg.module("layer_metrics", name).read(obs)

    assert T.program_ms(obs, 0) == pytest.approx(1.0)
    assert T.program_ms(obs, 1) == pytest.approx(3.0)
    # a kernel's time goes to the program it started in
    assert T.kernel_ms(obs, 0, (T.MOE_KERNEL,)) == pytest.approx(0.4)
    assert T.kernel_ms(obs, 1, (T.MOE_KERNEL,)) == pytest.approx(2.0)
    assert T.kernel_ms(obs, 0, T.WALK_KERNELS) == pytest.approx(0.18)
    step = T.program_counts(obs, 0)
    assert step["rows"] == 3 and step["experts_touched"] == 5
    chunk = T.program_counts(obs, 1)
    assert chunk["rows"] == 12 and chunk["pairs_held"] == 20
    # a share of a roofline takes what the TRACED programs moved (the seated
    # slots swing inside a window), over the traced programs' time
    step = T.program_counts(obs, 0, T.TRACED)
    assert step["rows"] == 2 and step["experts_touched"] == 3
    chunk = T.program_counts(obs, 1, T.TRACED)
    assert chunk["rows"] == 8 and chunk["pairs_held"] == 10
    assert read("decode_hbm_mfu_pct.trinity") == pytest.approx(
        100 * T.program_bytes(cfg, step, cfg["slots"]) / 819e9 / 1e-3)
    assert read("chunk_mfu_pct.trinity") == pytest.approx(100 * max(
        T.program_bytes(cfg, chunk, 1) / 819e9,
        T.program_flops(cfg, chunk, 1) / 197e12) / 3e-3)
    assert read("moe_expert_roofline_pct.trinity") == pytest.approx(
        100 * T.expert_bytes(cfg, 3) / 819e9 / 0.4e-3)
    assert read("attn_walk_roofline_pct.trinity") == pytest.approx(
        100 * 256 * 180 / 819e9 / 0.18e-3)
    # a counter's own metric is the window's
    assert read("experts_touched_pct.trinity") == pytest.approx(
        100 * 5 / (4 * 4))
    # no counters at the trace's edges (an untraced run's table): no share
    for name in NEW_METRICS[:4]:
        assert reg.module("layer_metrics", name).read(
            dict(obs, traced_counters=None)) is None
    assert read("window_pages_reused_per_s") == pytest.approx(15.0)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_readers_return_none_on_a_program_without_the_counters(toy_root, name):
    """What the parent gives: no device trace read, no such counters: None,
    not 0; the line leaves the metric out and nothing raises."""
    reg = Registry(toy_root)
    read = reg.module("layer_metrics", name).read
    cfg = reg.config("transformer_base_lm")
    bare = {"config": cfg, "peak": lambda key: 819e9, "trace": None,
            "histograms": {}, "seconds": 40.0}
    assert read(bare) is None
    # another family's counters (no labels), and a trace without the programs
    other = dict(bare, busy_s=1e-3, traced_window_s=2e-3,
                 trace={"planes": {"/device:TPU:0": {
                     "XLA Ops": [["fusion.1 fusion f32[4]", 10, 100]],
                     "XLA Modules": [["jit_train(1)", 0, 1000]]}}},
                 window_counters={"serving.decode.steps": 10,
                                  "serving.decode.moe.pairs": 100})
    assert read(other) is None


def test_device_readers_return_none_where_their_kernels_are_absent(toy_root):
    """The family's counters are there but the trace holds no grouped product
    and no walk inside a decode step: None, not 0."""
    reg = Registry(toy_root)
    trace = _trace()
    trace["planes"]["/device:TPU:0"]["XLA Ops"] = [
        ["fusion.1 fusion f32[4]", 1000, 100]]
    obs = _observed(reg.config(NAME), trace=trace, busy_s=1e-3,
                    traced_window_s=2e-3)
    for name in ("moe_expert_roofline_pct.trinity",
                 "attn_walk_roofline_pct.trinity"):
        assert reg.module("layer_metrics", name).read(obs) is None


# -- the configuration's file against the catalog ------------------------------

def _catalog():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Trinity-Large-Preview":
                return row
    pytest.skip("the catalog has no such entry")


def test_the_configuration_is_the_catalogs_entry_cut_as_it_says():
    with open(os.path.join(ROOT, CONFIG)) as f:
        cfg = json.load(f)
    entry = next(c for c in Registry(ROOT).bench["configs"]
                 if c["name"] == NAME)
    assert entry["reduced"] == cfg["reduced"] == sorted(
        cfg["reduced_why"], key=cfg["reduced"].index)
    row = _catalog()
    assert entry["source"] == row["source_url"]
    assert cfg["published"] == row["config"]
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == set(cfg["reduced"])
    # the cut the issue states: layers 5-9, one leading dense layer, 32 of 256
    # experts, an eighth of the vocabulary; no width, head count or window
    assert cfg["layer_types"] == row["config"]["layer_types"][5:10]
    assert cfg["kept_layers"] == [5, 6, 7, 8, 9]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"]) == (5, 1)
    assert cfg["experts_held"] == [0, 32] and cfg["router_experts"] == 256
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    for key in ("qk_norm", "attention_gate", "sandwich_norm",
                "rotary_on_window_layers_only", "expert_bias", "num_pages"):
        assert key in cfg["assumed"]
    for word in ("96-chip", "12 pipeline stages", "EP8", "layers 5-9",
                 "experts 0-31", "0-25023"):
        assert word in cfg["stands_for"], word


def test_the_cell_and_its_metrics_are_in_benchmark_json():
    bench = Registry(ROOT).bench
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, TRAFFIC, 1)
    with open(os.path.join(ROOT, "chipbench/traffic/%s.json" % TRAFFIC)) as f:
        mix = json.load(f)
    # the issue's rate: four fifths of the swept knee
    assert mix["arrivals"] == "poisson"
    assert mix["rate_rps"] == pytest.approx(0.8 * mix["knee_rps"]) == 6.4
    assert (mix["prompt_len"]["median"], mix["prompt_len"]["sigma"],
            mix["prompt_len"]["min"], mix["prompt_len"]["max"]) == (
                1024, 1.2, 64, 16384)
    assert (mix["output_len"]["median"], mix["output_len"]["sigma"],
            mix["output_len"]["min"], mix["output_len"]["max"]) == (
                192, 0.7, 16, 768)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert os.path.exists(os.path.join(
            ROOT, "chipbench/layer_metrics/%s.py" % name))
    reported = {m["name"] for m in bench["end_to_end"]
                if CELL in m.get("workloads", [CELL])}
    assert reported == {"serve_tokens_per_s", "itl_p95_ms", "setup_s"}
    # the front door's metrics and the loop's, as the issue lists them
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed >= {
        "ttft_mean_ms.serve", "ttft_p50_ms.serve", "ttft_p95_ms.serve",
        "queue_wait_p95_ms", "generator_lag_p95_ms", "admit_ms", "host_gc_s",
        "prefill_chunk_ms", "chunk_iteration_share_pct", "chunk_program_ms",
        "decode_step_ms", "sched_iteration_ms", "sched_host_ms",
        "step_build_ms", "step_dispatch_ms", "step_commit_ms",
        "decode_wait_ms", "steps_overlapped_pct", "loop_unaccounted_pct",
        "device_idle_pct.serve", "setup_warmup_s"}
    assert by_name["window_pages_reused_per_s"]["moves"] == (
        "serve_tokens_per_s")
    # every per-layer metric the cell lists moves a metric the cell reports
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["moves"] in reported, m["name"]
