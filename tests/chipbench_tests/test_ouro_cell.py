"""The standing driver for a LOOPED model (``drivers/serve_standing_ut.py``),
its model builder (``models/ouro.py``), the plain reference and the six
per-layer readers through ``run.run_cell`` on a toy checkout at toy widths on
the CPU, at ``--trace 0`` and ``1``; the controls of the cell's limits (an
8-bit K/V row; a cache that keeps ONE loop step's rows for all) each come out
not correct by a limit that names them; the contract on that checkout; the
readers on hand-made observations; and the configuration's own file against
the catalog's facts.  No test needs a chip."""
import ast
import json
import os
import shutil
import sys

import numpy as np
import pytest

import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import paddle_tpu as fluid  # noqa: E402
from chipbench import contract, kanana_decode, ouro_decode, run  # noqa: E402
from chipbench.registry import Registry  # noqa: E402

NEW_METRICS = {"ouro_attn_decode_ms", "ouro_attn_roofline_pct",
               "ouro_weights_roofline_pct", "ouro_reread_share_pct",
               "ouro_served_step_mean", "decode_hbm_mfu_pct.ouro"}
_BENCH = Registry(ROOT).bench
CELL = next(m for m in _BENCH["per_layer"]
            if m["name"] == "ouro_reread_share_pct")["workloads"][0]
_ENTRY = next(w for w in _BENCH["workloads"] if w["name"] == CELL)
NAME, TRAFFIC = _ENTRY["config"], _ENTRY["traffic"]
CONFIG = next(c for c in _BENCH["configs"] if c["name"] == NAME)["file"]
# toy sizes in the family's own key names; a toy is not the model, so its
# published block is cut with it.  128-lane heads, as the chip's tiles want
TOY = dict(hidden_size=64, intermediate_size=96, num_attention_heads=2,
           num_key_value_heads=2, vocab_size=211)
TOY_SIZES = dict(weights_dtype="float32", kv_dtype="float32", slots=3,
                 max_seq_len=512, page=8, chunk=16, buckets=[8, 16, 512],
                 num_pages=200, kept_layers=[0, 1, 2])


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("toy_ouro"))
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    path = os.path.join(root, CONFIG)
    with open(path) as f:
        cfg = json.load(f)
    cfg.update(TOY, **TOY_SIZES, num_hidden_layers=3, max_window_layers=3,
               layer_types=["full_attention"] * 3,
               published=dict(cfg["published"], **TOY))
    with open(path, "w") as f:
        json.dump(cfg, f)
    path = os.path.join(root, "chipbench/traffic/%s.json" % TRAFFIC)
    with open(path) as f:
        mix = json.load(f)
    mix.update(requests=3, max_prompt=60, setup_limit_s=300, trace_s=0.3,
               prompt_len={"dist": "lognormal", "median": 40, "sigma": 0.3,
                           "min": 24, "max": 60},
               output_len={"dist": "constant", "value": 440, "max": 440})
    with open(path, "w") as f:
        json.dump(mix, f)
    return root


def test_toy_checkout_is_within_the_contract(toy_root):
    assert contract.violations(toy_root) == []


def test_the_real_checkout_is_within_the_contract():
    assert contract.violations(ROOT) == []


def _held(log):
    return ast.literal_eval(log.split("standing: served state ", 1)[1].split(
        "; mechanism errors", 1)[0].replace("inf", "1e999"))


def _checks(log):
    return ast.literal_eval(log.split("; checks ", 1)[1].split("\n", 1)[0])


def _model(toy_root):
    return kanana_decode.builder(Registry(toy_root).config(NAME))


@pytest.mark.parametrize("trace", [0, 1])
def test_standing_ut_driver_at_toy_widths(toy_root, trace, capsys):
    out = run.run_cell(CELL, 2 ** 31 + 5, 0.25, trace, fluid.CPUPlace(),
                       root=toy_root)
    log = capsys.readouterr().out
    assert out["correct"] is True and out["failed"] == 0, log[-3000:]
    assert out["attempted"] == 3
    held, model = _held(log), _model(toy_root)
    # every limit has its control's reading on its far side
    assert held["kv_rows"] <= model.SERVED_STATE_TOL["kv_rows"] < held[
        "kv_rows_8bit"]
    assert held["kv_rows_deep_max"] < model.DEEP_ROW_TOL < held[
        "kv_rows_deep_8bit_min"]
    # (at the toy's 12 layer applications of 2 heads the 8-bit logits sit
    # about at the limit the cell's 48 set; its own float32 rows read ten
    # thousand times less)
    sound = max(e for c in _checks(log) for e in c["logit_err"])
    assert 0 < 1000 * sound < held["logits_8bit_rows"]
    assert sound < model.LOGIT_TOL < held["logits_shared_step_rows"]
    # the gates ride the loop's routing slot: every replayed row's within
    for c in _checks(log):
        assert c["routing"][0] == 1.0 and c["routing"][1] < model.GATE_TOL
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
    assert {"ouro_reread_share_pct", "ouro_served_step_mean",
            "history_chunk_tokens_per_s", "decode_step_ms", "decode_wait_ms",
            "sched_iteration_ms", "sched_host_ms", "setup_warmup_s",
            "setup_trace_lower_s", "loop_compile_requests"} <= got
    assert out["metrics"]["ouro_served_step_mean"]["value"] == 4.0
    assert 0 < out["metrics"]["ouro_reread_share_pct"]["value"] < 75
    assert not got & {"ouro_attn_decode_ms", "ouro_attn_roofline_pct",
                      "ouro_weights_roofline_pct", "decode_hbm_mfu_pct.ouro"}


def _rounded(M, monkeypatch, after):
    def wrap(step):
        def rounded(*args, **kwargs):
            out = step(*args, **kwargs)
            return (out[0], after(out[1])) + tuple(out[2:])
        return rounded

    for name in ("decode_step", "prefill_chunk"):
        monkeypatch.setattr(M, name, wrap(getattr(M, name)))


CONTROLS = {
    # K and V rows kept in 8 bits: the precision below the configuration's
    "eight_bit_rows": (
        lambda cfg: lambda cache: {
            name: leaf.astype("float8_e4m3fn").astype(leaf.dtype)
            for name, leaf in cache.items()},
        ("kv_rows", "kv_rows_deep")),
    # the paper's K/V sharing: every loop step reads the last step's rows
    "one_steps_rows_for_all": (
        lambda cfg: lambda cache: {
            name: jnp.tile(leaf[-cfg["num_hidden_layers"]:],
                           (cfg["total_ut_steps"], 1, 1, 1))
            for name, leaf in cache.items()},
        ("kv_rows_deep",)),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_control_comes_out_not_correct(toy_root, monkeypatch, capsys,
                                         control):
    """Step programs that keep their rows in 8 bits, or keep one loop step's
    rows for all four, fail the cell by the readings taken from the engine's
    own programs on its own cache; the stand-alone kernels, which bring their
    own pools, do not see it."""
    from paddle_tpu.models import ouro as M

    make, past = CONTROLS[control]
    cfg = Registry(toy_root).config(NAME)
    _rounded(M, monkeypatch, make(cfg))
    out = run.run_cell(CELL, 2 ** 31 + 9, 0.25, 0, fluid.CPUPlace(),
                       root=toy_root)
    assert out["correct"] is False and out["failed"] == 0
    log = capsys.readouterr().out
    assert "NOT CORRECT: the engine's own programs on its own cache" in log
    assert "NOT CORRECT: mechanisms" not in log
    held, model = _held(log), _model(toy_root)
    for name in past:
        assert held[name] > model.SERVED_STATE_TOL[name], (name, held)
    # the logits tell a wrong mechanism, not a lower precision (the builder's
    # LOGIT_TOL says so): one step's rows for all are far past their limit
    worst = max(e for c in _checks(log) for e in c["logit_err"])
    assert worst > (model.LOGIT_TOL if control == "one_steps_rows_for_all"
                    else 0.01)


# -- the readers on hand-made observations ------------------------------------

def _observed(cfg, counters, **more):
    return dict({"window_counters": counters, "config": cfg,
                 "peak": lambda key: {"hbm_bytes_per_s": 819e9}[key]}, **more)


def test_byte_counts_follow_the_configuration():
    cfg = Registry(ROOT).config(NAME)
    # 12 layers x 51.39 M parameters x 2 B, and the head's 100.7 M x 2 B
    assert abs(ouro_decode.layer_weight_bytes(cfg) - 1.2335e9) < 2e6
    assert abs(ouro_decode.head_bytes(cfg) - 0.2014e9) < 1e6
    assert ouro_decode.weight_bytes(cfg) == (
        4 * ouro_decode.layer_weight_bytes(cfg) + ouro_decode.head_bytes(cfg))
    # a row of K and V: 2 x 2048 x 2 B
    assert ouro_decode.kv_bytes(cfg, 1000, 48) == 8192 * 1048


def test_readers_on_hand_made_counters():
    cfg = Registry(ROOT).config(NAME)
    reg = Registry(ROOT)
    steps, live, rows = 100, 8, 7139
    counters = {"serving.decode.steps": steps,
                "serving.decode.ut.layer_applications": steps * live * 48,
                "serving.decode.ut.kv_rows_read": steps * rows * 48,
                "serving.decode.ut.served_step_sum": steps * live * 4}
    obs = _observed(cfg, counters)
    read = {n: reg.module("layer_metrics", n).read for n in NEW_METRICS}
    assert read["ouro_served_step_mean"](obs) == 4.0
    # the window's start: 3 x 1.23 GB of 7.9 GB (the issue reckoned 47%)
    assert 45 < read["ouro_reread_share_pct"](obs) < 49
    for name in NEW_METRICS - {"ouro_served_step_mean",
                               "ouro_reread_share_pct"}:
        assert read[name](obs) is None          # no device trace
    # a program without the counters (the parent): every reader is silent
    silent = _observed(cfg, {"serving.decode.steps": steps})
    assert all(read[n](silent) is None for n in NEW_METRICS)


def test_walk_instructions_are_found_in_the_loops_body():
    text = "\n".join([
        'ENTRY %main {',
        '  %a.1 = f32[8] fusion(%p), metadata={op_name="jit(decode)/embed"}',
        '}',
        '%body {',
        '  %ouro.attn.3 = bf16[8,1,2048] custom-call(%x), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(decode)/ouro.loop/while/'
        'body/closed_call/ouro.attn/pallas_call"}',
        '  %fusion.7 = f32[8,2048] fusion(%y), metadata={op_name="jit(decode)'
        '/ouro.loop/while/body/closed_call/ouro.attn/dot_general"}',
        '  %fusion.9 = f32[8,2048] fusion(%y), metadata={op_name="jit(decode)'
        '/ouro.loop/while/body/closed_call/ouro.mlp/mul"}',
        '  %cc.2 = f32[8] custom-call(%y), metadata={op_name="jit(decode)/'
        'ouro.attn/pallas_call"}',
        '}'])
    names = ouro_decode.stage_names(text)
    assert names["walk"] == ["ouro.attn.3"]
    assert names["attn"] == ["fusion.7", "ouro.attn.3"]
    assert names["mlp"] == ["fusion.9"] and names["loop_end"] == []


# -- the configuration's own file ---------------------------------------------

def test_configuration_states_the_catalogs_row():
    cfg = Registry(ROOT).config(NAME)
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["hidden_size"],
            pub["num_attention_heads"], pub["num_key_value_heads"],
            pub["head_dim"], pub["intermediate_size"], pub["vocab_size"],
            pub["total_ut_steps"], pub["early_exit_threshold"],
            pub["rope_theta"], pub["rms_norm_eps"]) == (
                48, 2048, 16, 16, 128, 5632, 49152, 4, 1, 1000000, 1e-6)
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "max_window_layers"]
    assert cfg["num_hidden_layers"] == 12 == len(cfg["layer_types"])
    assert cfg["kept_layers"] == list(range(12))
    assert cfg["total_ut_steps"] == 4 and cfg["early_exit_threshold"] == 1
    assert "four-stage ring" in cfg["stands_for"]
    assert "four-chip" in cfg["stands_for"]
    for key in ("no_bias", "sandwich_norms", "final_norm_in_the_loop",
                "exit_gate", "rotary", "kv_layer_numbering", "precision"):
        assert cfg["assumed"][key]


def test_mix_reserves_what_the_configuration_holds():
    from chipbench import traffic

    reg = Registry(ROOT)
    cfg, mix = reg.config(NAME), reg.traffic(TRAFFIC)
    assert mix["requests"] == cfg["slots"] == 8
    reqs = traffic.requests(mix, mix["requests"], 2 ** 31 + 1,
                            cfg["vocab_size"])
    lens = sorted(len(p) for p, _ in reqs)
    assert lens == [306, 451, 573, 699, 844, 1030, 1308, 1928]
    assert all(n == 2816 for _, n in reqs)
    assert max(lens) + 2816 <= cfg["max_seq_len"]
    reserved = sum(-(-(n + 2816) // cfg["page"]) for n in lens)
    assert reserved == 468 < cfg["num_pages"]
    assert max(max(p) for p, _ in reqs) < cfg["vocab_size"]
