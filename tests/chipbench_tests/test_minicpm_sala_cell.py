"""The ``sala_longctx_decode`` cell on the CPU at toy widths: the standing
driver (``drivers/serve_standing.py``), the model builder
(``models/minicpm_sala.py``), the plain reference and the seven per-layer
readers through ``run.run_cell`` on a toy checkout, at ``--trace 0`` and
``1``; the control of the cell's precision limits (a cache leaf kept in
bfloat16 comes out not correct); the contract on that checkout; the readers on
hand-made observations; and the configuration's own file against the
catalog's facts.  No test needs
a chip."""
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
from chipbench import contract, run, sala_decode  # noqa: E402
from chipbench.registry import Registry  # noqa: E402

CELL = "sala_longctx_decode"
CONFIG = "chipbench/configs/minicpm_sala_9b.json"
# toy widths in the family's own key names; a toy is not the model, so its
# published block is cut with it
TOY = dict(hidden_size=64, intermediate_size=128, vocab_size=96,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
           dim_model_base=16, num_hidden_layers=4,
           mixer_types=["minicpm4", "lightning-attn", "lightning-attn",
                        "minicpm4"])
TOY_SIZES = dict(
    sparse_config=dict(kernel_size=4, kernel_stride=2, block_size=8, topk=2,
                       init_blocks=1, window_size=16, dense_len=32),
    weights_dtype="float32", kv_dtype="float32", slots=4, max_seq_len=1120,
    page=8, num_pages=561, chunk=16, buckets=[8, 16, 96],
    # what the hand-made trace below holds a step: the kernel and one
    # selection fusion; one state update
    decode_step_ops={"sparse": 2, "state": 1})
NEW_METRICS = {"sparse_attn_decode_ms", "linear_state_decode_ms",
               "sparse_attn_roofline_pct", "linear_state_roofline_pct",
               "decode_hbm_mfu_pct.sala", "sparse_selected_share_pct",
               "history_prefill_tokens_per_s"}


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("toy_sala"))
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    path = os.path.join(root, CONFIG)
    with open(path) as f:
        cfg = json.load(f)
    published = dict(cfg["published"], **{
        k: v for k, v in TOY.items()
        if k not in ("num_hidden_layers", "mixer_types")})
    published["mixer_types"] = TOY["mixer_types"] * 2
    published["num_hidden_layers"] = 8
    cfg.update(TOY, **TOY_SIZES, published=published)
    with open(path, "w") as f:
        json.dump(cfg, f)
    path = os.path.join(root, "chipbench/traffic/standing_longctx.json")
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
def test_standing_driver_at_toy_widths(toy_root, trace):
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
    assert {"sparse_selected_share_pct", "history_prefill_tokens_per_s",
            "decode_step_ms", "decode_wait_ms", "sched_iteration_ms",
            "sched_host_ms", "setup_warmup_s"} <= got
    assert 0 < out["metrics"]["sparse_selected_share_pct"]["value"] < 100
    assert not got & {"sparse_attn_decode_ms", "linear_state_decode_ms",
                      "decode_hbm_mfu_pct.sala"}


def _leaves_in_bfloat16(step, leaves):
    """``step`` (a model step function) with ``leaves`` of the cache it
    returns rounded to bfloat16: what a cache that KEPT them in bfloat16
    would hold after every program."""
    import jax

    def rounded(*args, **kwargs):
        out = step(*args, **kwargs)
        cache = dict(out[1])
        for name in leaves:
            cache[name] = jax.lax.reduce_precision(cache[name], 8, 7)
        return (out[0], cache) + tuple(out[2:])

    return rounded


@pytest.mark.parametrize("leaf, readings", [
    ("lin", ["lightning_carry_decode", "lightning_carry_chunk"]),
    ("kbar", ["pooled_keys"])])
def test_a_bfloat16_cache_leaf_comes_out_not_correct(toy_root, monkeypatch,
                                                     capsys, leaf, readings):
    """The control of ``SERVED_STATE_TOL``: the lightning state or the pooled
    keys kept in bfloat16 (the precision below the float32 the configuration
    states) fail the cell through ``run_cell``, by the readings taken from
    the engine's own programs on its own cache and by no other limit."""
    from paddle_tpu.models import minicpm_sala as M

    for name in ("sala_decode_step", "sala_prefill_chunk"):
        monkeypatch.setattr(M, name, _leaves_in_bfloat16(getattr(M, name),
                                                         [leaf]))
    out = run.run_cell(CELL, 2 ** 31 + 9, 0.25, 0, fluid.CPUPlace(),
                       root=toy_root)
    assert out["correct"] is False and out["failed"] == 0
    model = sala_decode.builder(Registry(toy_root).config("minicpm_sala_9b"))
    log = capsys.readouterr().out
    assert log.count("NOT CORRECT") == 1
    held = ast.literal_eval(log.split("standing: served state ", 1)[1].split(
        "; mechanism errors", 1)[0].replace("inf", "1e999"))
    for name, limit in model.SERVED_STATE_TOL.items():
        if name in readings:
            assert held[name] > 10 * limit, held
        else:
            assert held[name] < limit / 10, held


def _observed(config, **more):
    base = {"config": config, "peak": lambda key: 819e9,
            "window_counters": {
                "serving.decode.steps": 10,
                "serving.decode.sparse.selected_tokens": 10 * 1000,
                "serving.decode.sparse.visible_tokens": 10 * 4000},
            "active_slots": 4}
    base.update(more)
    return base


def _trace(config):
    """A hand-made trace of two decode steps: per step the kernel's custom call of
    300 us, a selection op of 100 us (a dimension of MP blocks), a state
    update of 200 us and a matmul of 400 us."""
    mp = -(-config["max_seq_len"] // config["page"])
    state = "f32[6,4,%d,%d,%d]" % (config["lightning_nh"],
                                   config["lightning_head_dim"],
                                   config["lightning_head_dim"])
    ops, mods, t = [], [], 1000
    for _ in range(2):
        mods.append(["jit_decode(123)", t, 1000_000])
        for name, dur in (("paged_gqa_decode_attention.7 custom-call f32[4,2,2,16]",
                           300_000),
                          ("fusion.1 fusion f32[4,2,%d]" % mp, 100_000),
                          ("dus_fusion.2 fusion " + state, 200_000),
                          ("fusion.3 fusion bf16[4,64]", 400_000)):
            ops.append([name, t, dur])
            t += dur
        t += 500_000
    return {"planes": {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods}}}


def test_device_readers_on_a_hand_made_trace(toy_root):
    reg = Registry(toy_root)
    cfg = reg.config("minicpm_sala_9b")
    trace = _trace(cfg)
    obs = _observed(cfg, trace=trace, busy_s=2e-3, traced_window_s=3e-3)

    def read(name):
        return reg.module("layer_metrics", name).read(obs)

    assert read("sparse_attn_decode_ms") == pytest.approx(0.4)
    assert read("linear_state_decode_ms") == pytest.approx(0.2)
    model = sala_decode.builder(cfg)
    sparse = model.sparse_bytes(cfg, 1000, 4000)
    state = model.state_bytes(cfg, 4)
    assert read("sparse_attn_roofline_pct") == pytest.approx(
        100 * sparse / 819e9 / 0.4e-3)
    assert read("linear_state_roofline_pct") == pytest.approx(
        100 * state / 819e9 / 0.2e-3)
    assert read("decode_hbm_mfu_pct.sala") == pytest.approx(
        100 * (model.weight_bytes(cfg) + sparse + state) / 819e9 / 1e-3)
    assert read("sparse_selected_share_pct") == pytest.approx(25.0)


def test_device_readers_refuse_a_program_whose_operations_moved(toy_root):
    """A decode program in which the shapes take another number of
    instructions than the configuration commits (a later PR fused or
    reshaped one): the times and their shares are left out, not read as the
    layer moving; the whole step's share still reports."""
    reg = Registry(toy_root)
    cfg = reg.config("minicpm_sala_9b")
    trace = _trace(cfg)
    mp = -(-cfg["max_seq_len"] // cfg["page"])
    ops = trace["planes"]["/device:TPU:0"]["XLA Ops"]
    ops.append(["fusion.9 fusion f32[4,2,%d]" % mp, ops[-1][1], 1000])
    obs = _observed(cfg, trace=trace, busy_s=2e-3, traced_window_s=3e-3)
    assert len(sala_decode.matched_ops(obs, "sparse")) == 3
    for name, there in (("sparse_attn_decode_ms", False),
                        ("sparse_attn_roofline_pct", False),
                        ("linear_state_decode_ms", True),
                        ("decode_hbm_mfu_pct.sala", True)):
        value = reg.module("layer_metrics", name).read(obs)
        assert (value is not None) is there, name


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_readers_return_none_on_a_program_without_the_counters(toy_root, name):
    """What the parent gives: no device trace read, no counters, no set-up
    record: the line leaves the metric out and nothing raises."""
    reg = Registry(toy_root)
    obs = {"config": reg.config("transformer_base_lm"),
           "peak": lambda key: 819e9, "trace": None, "histograms": {}}
    assert reg.module("layer_metrics", name).read(obs) is None


def test_byte_counts_of_the_real_configuration():
    cfg = Registry(ROOT).config("minicpm_sala_9b")
    model = sala_decode.builder(cfg)
    # 2 x 253.8 M (sparse) + 6 x 285.2 M (lightning) + the 300.8 M head in
    # bf16, and 64 rows of the embedding: 5.04 GB a step
    assert model.weight_bytes(cfg) == 2 * (
        2 * 4096 * 61952 + 6 * 4096 * 69632 + 4096 * 73448 + 64 * 4096)
    assert model.state_bytes(cfg, 64) == 2 * 64 * 6 * 32 * 128 * 128 * 4
    # 6144 selected of 16384 visible tokens, one slot, one layer, one head
    assert model.sparse_bytes(cfg, 6144, 16384) == (
        6144 * 128 * 2 * 2 + 16384 / 16 * 128 * 4)


def test_the_configuration_file_keeps_every_published_width():
    cfg = Registry(ROOT).config("minicpm_sala_9b")
    pub = cfg["published"]
    assert cfg["reduced"] == ["num_hidden_layers", "mixer_types"]
    for key in set(pub) - set(cfg["reduced"]):
        assert cfg[key] == pub[key], key
    assert cfg["mixer_types"] == [pub["mixer_types"][i]
                                  for i in cfg["kept_layers"]]
    assert cfg["mixer_types"] == (["minicpm4"] + ["lightning-attn"] * 3) * 2
    assert cfg["page"] == cfg["sparse_config"]["block_size"]
    assert cfg["mup_denominator"] == pub["num_hidden_layers"] == 32
