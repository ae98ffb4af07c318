"""The ``kanana2_standing_decode`` cell on the CPU at toy widths: the standing
driver for a latent cache and routed experts (``drivers/
serve_standing_moe.py``), the model builder (``models/deepseek_v3.py``), the
plain reference and the seven per-layer readers through ``run.run_cell`` on a
toy checkout, at ``--trace 0`` and ``1``; the controls of the cell's precision
limits (an 8-bit latent leaf and bfloat16 router scores each come out not
correct, by the limit that names them); the contract on that checkout; the
readers on hand-made observations; and the configuration's own file against
the catalog's facts.  No test needs a chip."""
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

CELL = "kanana2_standing_decode"
NAME = "kanana2_30b_a3b"
CONFIG = "chipbench/configs/%s.json" % NAME
# toy sizes in the family's own key names; a toy is not the model, so its
# published block is cut with it
TOY = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
           vocab_size=96, num_attention_heads=4, num_key_value_heads=4,
           head_dim=8, kv_lora_rank=32, qk_nope_head_dim=16,
           qk_rope_head_dim=8, qk_head_dim=24, v_head_dim=16,
           n_routed_experts=16, num_experts_per_tok=3, num_hidden_layers=3)
TOY_SIZES = dict(weights_dtype="float32", kv_dtype="float32", slots=4,
                 max_seq_len=1120, page=8, num_pages=561, chunk=16,
                 buckets=[8, 16, 96], kept_layers=[0, 1, 2])
NEW_METRICS = {"mla_decode_ms", "mla_decode_roofline_pct",
               "moe_expert_decode_ms", "moe_expert_roofline_pct",
               "experts_touched_pct", "expert_load_max_over_mean",
               "decode_hbm_mfu_pct.kanana"}


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("toy_kanana"))
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    path = os.path.join(root, CONFIG)
    with open(path) as f:
        cfg = json.load(f)
    published = dict(cfg["published"], **TOY)
    published["num_hidden_layers"] = 6
    cfg.update(TOY, **TOY_SIZES, published=published)
    with open(path, "w") as f:
        json.dump(cfg, f)
    path = os.path.join(root, "chipbench/traffic/standing_midctx.json")
    with open(path) as f:
        mix = json.load(f)
    mix.update(requests=4, max_prompt=96, setup_limit_s=300, trace_s=0.3,
               prompt_len={"dist": "lognormal", "median": 64, "sigma": 0.4,
                           "min": 40, "max": 96},
               output_len={"dist": "constant", "value": 1000, "max": 1000})
    with open(path, "w") as f:
        json.dump(mix, f)
    return root


def test_toy_checkout_is_within_the_contract(toy_root):
    assert contract.violations(toy_root) == []


@pytest.mark.parametrize("trace", [0, 1])
def test_standing_moe_driver_at_toy_widths(toy_root, trace):
    # a window short enough that no request reaches its 1000th token
    out = run.run_cell(CELL, 2 ** 31 + 5, 0.25, trace, fluid.CPUPlace(),
                       root=toy_root)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 4
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
    assert {"experts_touched_pct", "expert_load_max_over_mean",
            "history_prefill_tokens_per_s", "decode_step_ms",
            "decode_wait_ms", "sched_iteration_ms", "sched_host_ms",
            "setup_warmup_s"} <= got
    assert 0 < out["metrics"]["experts_touched_pct"]["value"] <= 100
    assert out["metrics"]["expert_load_max_over_mean"]["value"] >= 1
    assert not got & {"mla_decode_ms", "moe_expert_decode_ms",
                      "decode_hbm_mfu_pct.kanana"}


def _held(log):
    return ast.literal_eval(log.split("standing: served state ", 1)[1].split(
        "; mechanism errors", 1)[0].replace("inf", "1e999"))


def _mechanisms(log):
    return ast.literal_eval(log.split("; mechanism errors ", 1)[1].split(
        "; checks", 1)[0].replace("inf", "1e999"))


def test_an_eight_bit_latent_comes_out_not_correct(toy_root, monkeypatch,
                                                   capsys):
    """The control of ``SERVED_STATE_TOL``: latent rows kept in 8 bits (the
    precision below the 16 the configuration states) fail the cell through
    ``run_cell`` by the reading taken from the engine's own programs on its
    own cache; the stand-alone mechanisms, which bring their own pool, do
    not see it (at float32 toy widths the replayed logits do: nothing else
    rounds there)."""
    import jax.numpy as jnp

    from paddle_tpu.models import deepseek_v3 as M

    def eight_bit(step):
        def rounded(*args, **kwargs):
            out = step(*args, **kwargs)
            cache = dict(out[1])
            cache["latent"] = cache["latent"].astype(
                jnp.float8_e4m3fn).astype(cache["latent"].dtype)
            return (out[0], cache) + tuple(out[2:])
        return rounded

    for name in ("decode_step", "prefill_chunk"):
        monkeypatch.setattr(M, name, eight_bit(getattr(M, name)))
    out = run.run_cell(CELL, 2 ** 31 + 9, 0.25, 0, fluid.CPUPlace(),
                       root=toy_root)
    assert out["correct"] is False and out["failed"] == 0
    model = kanana_decode.builder(Registry(toy_root).config(NAME))
    log = capsys.readouterr().out
    assert "NOT CORRECT: the engine's own programs on its own cache" in log
    assert "NOT CORRECT: mechanisms" not in log
    held = _held(log)
    assert held["latent_rows"] > 2 * model.SERVED_STATE_TOL["latent_rows"]
    assert held["latent_padding"] == 0.0


def test_bfloat16_router_scores_come_out_not_correct(toy_root, monkeypatch,
                                                     capsys):
    """The control of ``routing_mismatch``: a router that scores from
    bfloat16 operands (the precision below the float32 the configuration
    states) fails the cell by that limit; every other mechanism's limit
    holds (a row routed to another expert can move the replayed logits
    too)."""
    from paddle_tpu.parallel import moe

    model = kanana_decode.builder(Registry(toy_root).config(NAME))
    real = moe.route_topk

    def coarse(x, w, bias, *, top_k, **kw):
        import jax
        import jax.numpy as jnp

        experts = model._route_bf16(x, w, bias, top_k)
        weights = jnp.take_along_axis(jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), w)), experts, axis=-1)
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
        return experts.astype(jnp.int32), weights * kw.get("scale", 1.0)

    monkeypatch.setattr(moe, "route_topk", coarse)
    out = run.run_cell(CELL, 2 ** 31 + 9, 0.25, 0, fluid.CPUPlace(),
                       root=toy_root)
    monkeypatch.setattr(moe, "route_topk", real)
    assert out["correct"] is False and out["failed"] == 0
    log = capsys.readouterr().out
    assert "NOT CORRECT: mechanisms vs reference" in log
    assert "NOT CORRECT: the engine's own programs" not in log
    errs = _mechanisms(log)
    assert errs["routing_mismatch"] > model.MECHANISM_RTOL["routing_mismatch"]
    for name in ("mla_decode", "mla_prefill", "moe_decode", "moe_prefill"):
        assert errs[name] <= model.MECHANISM_RTOL[name], errs


def test_executables_that_route_apart_from_the_replay_come_out_not_correct(
        toy_root, monkeypatch, capsys):
    """The control of ``latent_rows_deep``: the routing ``correct`` compares
    is the replay's (the step functions under a jit that returns it), and
    what holds the ENGINE'S executables to it is the rows they leave in the
    later layers.  A replay that routes the first expert layer apart from the
    engine's programs (one expert always chosen) fails the cell by that
    reading, though its own logits follow its own routing."""
    from paddle_tpu.models import deepseek_v3 as M

    def apart(step):
        def routed(p, *args, with_routing=False, **kw):
            if with_routing:
                p = dict(p, router_b=p["router_b"].at[0, 5].set(10.0))
            return step(p, *args, with_routing=with_routing, **kw)
        return routed

    for name in ("decode_step", "prefill_chunk"):
        monkeypatch.setattr(M, name, apart(getattr(M, name)))
    out = run.run_cell(CELL, 2 ** 31 + 9, 0.25, 0, fluid.CPUPlace(),
                       root=toy_root)
    assert out["correct"] is False and out["failed"] == 0
    model = kanana_decode.builder(Registry(toy_root).config(NAME))
    log = capsys.readouterr().out
    assert "NOT CORRECT: the engine's own programs on its own cache" in log
    assert "NOT CORRECT: mechanisms" not in log
    held = _held(log)
    assert held["latent_rows_deep"] > 5 * model.SERVED_STATE_TOL[
        "latent_rows_deep"]
    assert held["latent_rows"] <= model.SERVED_STATE_TOL["latent_rows"]


def _observed(config, **more):
    base = {"config": config, "peak": lambda key: 819e9,
            "window_counters": {
                "serving.decode.steps": 10,
                "serving.decode.moe.pairs": 10 * 2 * 12,
                "serving.decode.moe.experts_touched": 10 * 2 * 8,
                "serving.decode.moe.max_load": 10 * 2 * 3,
                "serving.decode.latent.tokens_read": 10 * 3 * 400},
            "active_slots": 4}
    base.update(more)
    return base


def _trace():
    """A hand-made trace of two decode steps: per step three attention custom
    calls of 100 us, four grouped products of 50 us and a matmul of 500 us."""
    ops, mods, t = [], [], 1000
    for _ in range(2):
        mods.append(["jit_decode(123)", t, 1000_000])
        calls = [("paged_mla_attention.%d custom-call f32[4,16,32]" % i,
                  100_000) for i in range(3)]
        calls += [("moe_grouped_matmul.%d custom-call f32[16,64]" % i, 50_000)
                  for i in range(4)]
        for name, dur in calls + [("fusion.3 fusion bf16[4,64]", 500_000)]:
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

    assert read("mla_decode_ms") == pytest.approx(0.3)
    assert read("moe_expert_decode_ms") == pytest.approx(0.2)
    model = kanana_decode.builder(cfg)
    latent = model.latent_bytes(cfg, 1200)
    experts = model.expert_bytes(cfg, 16)
    assert latent == 4 * (32 + 8) * 1200
    assert experts == 4 * 3 * 64 * 32 * 16
    assert read("mla_decode_roofline_pct") == pytest.approx(
        100 * latent / 819e9 / 0.3e-3)
    assert read("moe_expert_roofline_pct") == pytest.approx(
        100 * experts / 819e9 / 0.2e-3)
    assert read("decode_hbm_mfu_pct.kanana") == pytest.approx(
        100 * (model.weight_bytes(cfg) + latent + experts) / 819e9 / 1e-3)
    assert read("experts_touched_pct") == pytest.approx(100 * 16 / 32)
    assert read("expert_load_max_over_mean") == pytest.approx(6 / (24 / 16))


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
    D, H, V = cfg["hidden_size"], cfg["num_attention_heads"], cfg["vocab_size"]
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    attn = (D * (H * cfg["qk_head_dim"] + row)
            + H * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            * cfg["kv_lora_rank"] + H * cfg["v_head_dim"] * D)
    expert = 3 * D * cfg["moe_intermediate_size"]
    # what every step reads: every attention block, the dense block, the
    # shared experts, the head and a row of the embedding a slot in bf16; the
    # routers (with their bias) in f32
    assert model.weight_bytes(cfg) == 2 * (
        cfg["num_hidden_layers"] * attn
        + cfg["first_k_dense_replace"] * 3 * D * cfg["intermediate_size"]
        + n_moe * cfg["n_shared_experts"] * expert + D * V
        + cfg["slots"] * D) + 4 * n_moe * (D + 1) * cfg["n_routed_experts"]
    assert model.expert_params(cfg) == expert
    assert model.expert_bytes(cfg, 7) == 2 * 7 * expert
    assert model.latent_bytes(cfg, 11) == 11 * 2 * row
    # the whole stage as the configuration's file states it: 3.79 B weights
    total = (model.weight_bytes(cfg) - 4 * n_moe * (D + 1) * cfg[
        "n_routed_experts"]) // 2 - cfg["slots"] * D + D * V + n_moe * (
            cfg["n_routed_experts"] * expert + D * cfg["n_routed_experts"])
    assert round(total / 1e9, 2) == 3.79


def test_the_configuration_file_keeps_every_published_size():
    cfg = Registry(ROOT).config(NAME)
    pub = cfg["published"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = [r for r in rows if r["source_url"] == cfg["source"].split(" ")[0]]
    if row:     # the catalog, where this machine has it
        assert pub == row[0]["config"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    for key in set(pub) - set(cfg["reduced"]):
        assert cfg[key] == pub[key], key
    assert cfg["kept_layers"] == list(range(cfg["num_hidden_layers"]))
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert pub["num_hidden_layers"] % cfg["num_hidden_layers"] == 0
