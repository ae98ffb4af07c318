"""The standing driver for a cache in page GROUPS (``drivers/
serve_standing_swa.py``), its model builder (``models/mellum.py``), the plain
reference and the nine per-layer readers through ``run.run_cell`` on a toy
checkout at toy widths on the CPU, at ``--trace 0`` and ``1``; the controls of
the cell's limits (an 8-bit K/V row, bfloat16 router scores, a window one
short, plain rotary in a full layer and a missing ``attention_factor`` each
come out not correct, by a limit that names them); the contract on that
checkout; the readers on hand-made observations; and the configuration's own
file against the catalog's facts.  No test needs a chip."""
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
from chipbench.registry import Registry  # noqa: E402

NEW_METRICS = {"full_attn_decode_ms", "window_attn_decode_ms",
               "full_attn_roofline_pct", "window_attn_roofline_pct",
               "window_read_share_pct", "moe_expert_roofline_pct.mellum",
               "experts_touched_pct.mellum",
               "expert_load_max_over_mean.mellum",
               "decode_hbm_mfu_pct.mellum"}
# the cell and the configuration that report them, from BENCHMARK.json itself
_BENCH = Registry(ROOT).bench
CELL = next(m for m in _BENCH["per_layer"]
            if m["name"] == "window_read_share_pct")["workloads"][0]
_ENTRY = next(w for w in _BENCH["workloads"] if w["name"] == CELL)
NAME, TRAFFIC = _ENTRY["config"], _ENTRY["traffic"]
CONFIG = next(c for c in _BENCH["configs"] if c["name"] == NAME)["file"]
# toy sizes in the family's own key names; a toy is not the model, so its
# published block is cut with it
KINDS = ["sliding_attention", "sliding_attention", "full_attention",
         "sliding_attention"]
TOY = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
           vocab_size=96, num_attention_heads=4, num_key_value_heads=2,
           head_dim=16, num_experts=16, num_experts_per_tok=3,
           sliding_window=24, max_position_embeddings=2048,
           rope_parameters={
               "full_attention": {
                   "rope_type": "yarn", "rope_theta": 10000, "factor": 4,
                   "original_max_position_embeddings": 64, "beta_fast": 32,
                   "beta_slow": 1, "attention_factor": 1.1386294361119891},
               "sliding_attention": {"rope_type": "default",
                                     "rope_theta": 10000}})
TOY_SIZES = dict(weights_dtype="float32", kv_dtype="float32", slots=4,
                 max_seq_len=1120, page=8, chunk=16, buckets=[8, 16, 112],
                 num_pages={"full": 561, "window": 25},
                 kept_layers=[0, 1, 2, 3])


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("toy_mellum"))
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    path = os.path.join(root, CONFIG)
    with open(path) as f:
        cfg = json.load(f)
    published = dict(cfg["published"], **TOY)
    published.update(num_hidden_layers=8, layer_types=KINDS * 2,
                     mlp_layer_types=["sparse"] * 8)
    cfg.update(TOY, **TOY_SIZES, published=published, num_hidden_layers=4,
               layer_types=KINDS, mlp_layer_types=["sparse"] * 4)
    with open(path, "w") as f:
        json.dump(cfg, f)
    path = os.path.join(root, "chipbench/traffic/%s.json" % TRAFFIC)
    with open(path) as f:
        mix = json.load(f)
    mix.update(requests=4, max_prompt=112, setup_limit_s=300, trace_s=0.3,
               prompt_len={"dist": "lognormal", "median": 64, "sigma": 0.6,
                           "min": 20, "max": 112},
               output_len={"dist": "constant", "value": 1000, "max": 1000})
    with open(path, "w") as f:
        json.dump(mix, f)
    return root


def test_toy_checkout_is_within_the_contract(toy_root):
    assert contract.violations(toy_root) == []


@pytest.mark.parametrize("trace", [0, 1])
def test_standing_groups_driver_at_toy_widths(toy_root, trace, capsys):
    # a window short enough that no request reaches its 1000th token
    out = run.run_cell(CELL, 2 ** 31 + 5, 0.25, trace, fluid.CPUPlace(),
                       root=toy_root)
    log = capsys.readouterr().out
    assert out["correct"] is True and out["failed"] == 0, log[-3000:]
    assert out["attempted"] == 4
    # the check's own schedule took pages a second time that it had given back
    assert _held(log)["window_pages_reused"] > 0
    reg = Registry(toy_root)
    table = "per_layer" if trace else "end_to_end"
    units = {m["name"]: m["unit"] for m in reg.metrics(table, CELL)}
    for name, m in out["metrics"].items():
        assert m["unit"] == units[name] and np.isfinite(m["value"]), name
    if not trace:
        assert set(out["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                       "setup_s"}
        assert out["metrics"]["serve_tokens_per_s"]["value"] > 0
        return
    # no device in a CPU trace: the device readers leave their metrics out,
    # the counter and span readers report
    got = set(out["metrics"])
    assert {"experts_touched_pct.mellum", "expert_load_max_over_mean.mellum",
            "window_read_share_pct", "history_prefill_tokens_per_s",
            "decode_step_ms", "decode_wait_ms", "sched_iteration_ms",
            "sched_host_ms", "setup_warmup_s"} <= got
    assert 0 < out["metrics"]["experts_touched_pct.mellum"]["value"] <= 100
    assert out["metrics"]["expert_load_max_over_mean.mellum"]["value"] >= 1
    # contexts of 20..112 and more against a window of 24: the walk is bounded
    assert 0 < out["metrics"]["window_read_share_pct"]["value"] < 70
    assert not got & {"full_attn_decode_ms", "window_attn_decode_ms",
                      "moe_expert_decode_ms", "decode_hbm_mfu_pct.mellum"}


def _held(log):
    return ast.literal_eval(log.split("standing: served state ", 1)[1].split(
        "; mechanism errors", 1)[0].replace("inf", "1e999"))


def _mechanisms(log):
    return ast.literal_eval(log.split("; mechanism errors ", 1)[1].split(
        "; checks", 1)[0].replace("inf", "1e999"))


def _model(toy_root):
    return kanana_decode.builder(Registry(toy_root).config(NAME))


def test_an_eight_bit_row_comes_out_not_correct(toy_root, monkeypatch, capsys):
    """The control of ``SERVED_STATE_TOL``'s ``kv_rows``: K and V rows kept
    in 8 bits (the precision below the 16 the configuration states) fail the
    cell by the reading taken from the engine's own programs on its own
    cache; the stand-alone mechanisms, which bring their own pools, do not
    see it."""
    import jax.numpy as jnp

    from paddle_tpu.models import mellum as M

    def eight_bit(step):
        def rounded(*args, **kwargs):
            out = step(*args, **kwargs)
            cache = {name: leaf.astype(jnp.float8_e4m3fn).astype(leaf.dtype)
                     for name, leaf in out[1].items()}
            return (out[0], cache) + tuple(out[2:])
        return rounded

    for name in ("decode_step", "prefill_chunk"):
        monkeypatch.setattr(M, name, eight_bit(getattr(M, name)))
    out = run.run_cell(CELL, 2 ** 31 + 9, 0.25, 0, fluid.CPUPlace(),
                       root=toy_root)
    assert out["correct"] is False and out["failed"] == 0
    log = capsys.readouterr().out
    assert "NOT CORRECT: the engine's own programs on its own cache" in log
    assert "NOT CORRECT: mechanisms" not in log
    assert _held(log)["kv_rows"] > 2 * _model(toy_root).SERVED_STATE_TOL[
        "kv_rows"]


def test_bfloat16_router_scores_come_out_not_correct(toy_root, monkeypatch,
                                                     capsys):
    """The control of ``routing_mismatch``: a router whose logits come from
    bfloat16 operands fails the cell by that limit; every other mechanism's
    limit holds."""
    from paddle_tpu.parallel import moe

    model = _model(toy_root)
    real = moe.route_topk

    def coarse(x, w, bias, *, top_k, **kw):
        import jax
        import jax.numpy as jnp

        experts = model._route_bf16(x, w, top_k)
        weights = jnp.take_along_axis(jax.nn.softmax(jnp.dot(
            x.astype(jnp.float32), w), axis=-1), experts, axis=-1)
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
        return experts.astype(jnp.int32), weights

    monkeypatch.setattr(moe, "route_topk", coarse)
    out = run.run_cell(CELL, 2 ** 31 + 9, 0.25, 0, fluid.CPUPlace(),
                       root=toy_root)
    monkeypatch.setattr(moe, "route_topk", real)
    assert out["correct"] is False and out["failed"] == 0
    log = capsys.readouterr().out
    assert "NOT CORRECT: mechanisms vs reference" in log
    errs = _mechanisms(log)
    assert errs["routing_mismatch"] > model.MECHANISM_RTOL["routing_mismatch"]
    for name in ("full_decode", "window_decode", "full_prefill",
                 "window_prefill", "moe_decode", "moe_prefill"):
        assert errs[name] <= model.MECHANISM_RTOL[name], errs


def test_a_window_one_short_or_one_long_reads_past_its_limit(toy_root,
                                                             capsys):
    """The control of ``window_decode`` / ``window_prefill``: the same kernel
    at a window of W - 1 and of W + 1, read in every run beside the sound
    one, lies past the limit that the sound one is under."""
    out = run.run_cell(CELL, 2 ** 31 + 9, 0.25, 0, fluid.CPUPlace(),
                       root=toy_root)
    assert out["correct"] is True
    errs, model = _mechanisms(capsys.readouterr().out), _model(toy_root)
    for name in ("window_decode", "window_prefill"):
        assert errs[name] <= model.MECHANISM_RTOL[name]
        for variant in ("_w1023", "_w1025"):
            assert errs[name + variant] > 2 * model.MECHANISM_RTOL[name], errs
    # and the rotary variants in kv_rows_deep's own measure
    assert errs["k_rows_plain_rotary"] > 2 * model.DEEP_ROW_TOL
    assert errs["k_rows_no_attention_factor"] > 2 * model.DEEP_ROW_TOL


@pytest.mark.parametrize("variant", ["plain_rotary", "no_attention_factor"])
def test_a_wrong_rotary_in_a_full_layer_comes_out_not_correct(
        toy_root, monkeypatch, capsys, variant):
    """The control of ``kv_rows_deep``: step programs whose FULL layers use
    the sliding layers' plain rotary, or YaRN's frequencies without the
    ``attention_factor``, leave K rows in the full group that the reference's
    are not, and the cell comes out not correct by that reading (layer 0, a
    sliding layer, still holds ``kv_rows``)."""
    from paddle_tpu.models import mellum as M

    real = M.rope_inverse_frequencies

    def wrong(rope, head_dim):
        inv, factor = real(rope, head_dim)
        if rope.get("rope_type") != "yarn":
            return inv, factor
        if variant == "no_attention_factor":
            return inv, 1.0
        return real({"rope_type": "default",
                     "rope_theta": rope["rope_theta"]}, head_dim)

    monkeypatch.setattr(M, "rope_inverse_frequencies", wrong)
    out = run.run_cell(CELL, 2 ** 31 + 9, 0.25, 0, fluid.CPUPlace(),
                       root=toy_root)
    monkeypatch.setattr(M, "rope_inverse_frequencies", real)
    assert out["correct"] is False and out["failed"] == 0
    log = capsys.readouterr().out
    assert "NOT CORRECT: the engine's own programs on its own cache" in log
    held, model = _held(log), _model(toy_root)
    assert held["kv_rows_deep"] > 5 * model.SERVED_STATE_TOL["kv_rows_deep"]
    assert held["kv_rows"] <= model.SERVED_STATE_TOL["kv_rows"]


def _observed(config, **more):
    base = {"config": config, "peak": lambda key: 819e9,
            "window_counters": {
                "serving.decode.steps": 10,
                "serving.decode.moe.pairs": 10 * 4 * 12,
                "serving.decode.moe.experts_touched": 10 * 4 * 8,
                "serving.decode.moe.max_load": 10 * 4 * 3,
                "serving.decode.kv.full_tokens_read": 10 * 1 * 400,
                "serving.decode.kv.window_tokens_read": 10 * 3 * 90},
            "active_slots": 4}
    base.update(more)
    return base


def _trace():
    """A hand-made trace of two decode steps: per step one full-attention
    custom call of 100 us, three window ones of 20 us, eight grouped products
    of 50 us and a matmul of 440 us."""
    ops, mods, t = [], [], 1000
    for _ in range(2):
        mods.append(["jit_decode(123)", t, 1000_000])
        calls = [("paged_gqa_full_attention.1 custom-call f32[4,32,16]",
                  100_000)]
        calls += [("paged_gqa_window_attention.%d custom-call f32[4,32,16]"
                   % i, 20_000) for i in range(3)]
        calls += [("moe_grouped_matmul.%d custom-call f32[16,64]" % i, 50_000)
                  for i in range(8)]
        for name, dur in calls + [("fusion.3 fusion bf16[4,64]", 440_000)]:
            ops.append([name, t, dur])
            t += dur
        t += 500_000
    return {"planes": {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods}}}


def test_device_readers_on_a_hand_made_trace(toy_root):
    reg = Registry(toy_root)
    cfg = reg.config(NAME)
    obs = _observed(cfg, trace=_trace(), busy_s=2e-3, traced_window_s=3e-3)

    def read(name):
        return reg.module("layer_metrics", name).read(obs)

    assert read("full_attn_decode_ms") == pytest.approx(0.1)
    assert read("window_attn_decode_ms") == pytest.approx(0.06)
    assert read("moe_expert_decode_ms") == pytest.approx(0.4)
    model = kanana_decode.builder(cfg)
    full, window = model.kv_bytes(cfg, 400, 270)
    assert (full, window) == (400 * 2 * 32 * 4, 270 * 2 * 32 * 4)
    experts = model.expert_bytes(cfg, 32)
    assert experts == 4 * 3 * 64 * 32 * 32
    assert read("full_attn_roofline_pct") == pytest.approx(
        100 * full / 819e9 / 0.1e-3)
    assert read("window_attn_roofline_pct") == pytest.approx(
        100 * window / 819e9 / 0.06e-3)
    assert read("window_read_share_pct") == pytest.approx(
        100 * 270 / (3 * 400))
    assert read("moe_expert_roofline_pct.mellum") == pytest.approx(
        100 * experts / 819e9 / 0.4e-3)
    assert read("decode_hbm_mfu_pct.mellum") == pytest.approx(
        100 * (model.weight_bytes(cfg) + full + window + experts)
        / 819e9 / 1e-3)
    assert read("experts_touched_pct.mellum") == pytest.approx(
        100 * 32 / (4 * 16))
    assert read("expert_load_max_over_mean.mellum") == pytest.approx(
        12 / (48 / 16))


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_readers_return_none_on_a_program_without_the_counters(toy_root, name):
    """What the parent gives: no device trace read, no counters: the line
    leaves the metric out and nothing raises."""
    reg = Registry(toy_root)
    obs = {"config": reg.config("transformer_base_lm"),
           "peak": lambda key: 819e9, "trace": None, "histograms": {}}
    assert reg.module("layer_metrics", name).read(obs) is None


def test_byte_counts_of_the_real_configuration():
    cfg = Registry(ROOT).config(NAME)
    model = kanana_decode.builder(cfg)
    D, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    H, Hkv, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    attn = D * (H + 2 * Hkv) * Dh + H * Dh * D
    expert = 3 * D * cfg["moe_intermediate_size"]
    assert attn == 21233664 and expert * cfg["num_experts"] == 396361728
    assert model.weight_bytes(cfg) == 2 * (
        L * attn + D * V + cfg["slots"] * D) + 4 * L * D * cfg["num_experts"]
    assert model.expert_params(cfg) == expert
    assert model.expert_bytes(cfg, 7) == 2 * 7 * expert
    assert model.kv_bytes(cfg, 11, 5) == (11 * 2048, 5 * 2048)
    # the whole stage as the configuration's file states it: 3.795 B weights
    total = L * (attn + D * cfg["num_experts"]
                 + cfg["num_experts"] * expert) + 2 * D * V
    assert round(total / 1e9, 3) == 3.795
    # the two groups' pages as the file reckons them
    pages = cfg["num_pages"]
    assert pages["window"] == cfg["slots"] * (
        -(-(cfg["sliding_window"] + cfg["chunk"]) // cfg["page"]) + 1) + 1
    assert pages["full"] * cfg["page"] * 2 * 2048 == 3767271424


def test_the_configuration_file_keeps_every_published_size():
    cfg = Registry(ROOT).config(NAME)
    pub = cfg["published"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):     # the catalog, where this machine has it
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
        row = [r for r in rows
               if r["source_url"] == cfg["source"].split(" ")[0]]
        assert row and pub == row[0]["config"]
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "mlp_layer_types"]
    for key in set(pub) - set(cfg["reduced"]):
        assert cfg[key] == pub[key], key
    n = cfg["num_hidden_layers"]
    assert cfg["kept_layers"] == list(range(n))
    assert cfg["layer_types"] == pub["layer_types"][:n]
    assert cfg["mlp_layer_types"] == pub["mlp_layer_types"][:n]
    # two whole periods: both kinds of layer, twice
    assert cfg["layer_types"].count("full_attention") == 2
    assert "MTP" in cfg["not_built"] and "QK-norm" in cfg["not_built"]
