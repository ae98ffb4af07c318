"""The standing driver for a BLOCK-DIFFUSION model
(``drivers/serve_standing_bd.py``), its model builder (``models/sdar.py``), the
plain reference and the seven per-layer readers through ``run.run_cell`` on a
toy checkout at toy widths on the CPU, at ``--trace 0`` and ``1``; the
controls of the cell's limits (an 8-bit K/V row; a causal mask inside the
block; a block's K/V kept from a denoising forward; a block's state read from
another slot, a fault that only the SERVED ids show) each come out not correct
by a limit that names them; the contract on that checkout; the readers on
hand-made observations; and the configuration's own file against the catalog's
facts.  No test needs a chip."""
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
from chipbench import contract, kanana_decode, run, sdar_decode  # noqa: E402
from chipbench.registry import Registry  # noqa: E402

NEW_METRICS = {"diffusion_tokens_per_forward", "diffusion_kv_forward_share_pct",
               "diffusion_block_ms", "sdar_attn_roofline_pct", "moe_expert_roofline_pct.sdar",
               "experts_touched_pct.sdar", "decode_hbm_mfu_pct.sdar"}
_BENCH = Registry(ROOT).bench
CELL = next(m for m in _BENCH["per_layer"]
            if m["name"] == "diffusion_tokens_per_forward")["workloads"][0]
_ENTRY = next(w for w in _BENCH["workloads"] if w["name"] == CELL)
NAME, TRAFFIC = _ENTRY["config"], _ENTRY["traffic"]
CONFIG = next(c for c in _BENCH["configs"] if c["name"] == NAME)["file"]
# toy sizes in the family's own key names; a toy is not the model, so its
# published block is cut with it.  128-lane heads, as the chip's tiles want
TOY = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
           num_attention_heads=4, num_key_value_heads=2, num_experts=16,
           num_experts_per_tok=4, vocab_size=211)
TOY_SIZES = dict(weights_dtype="float32", kv_dtype="float32", slots=3,
                 max_seq_len=512, page=8, chunk=16, buckets=[8, 16, 512],
                 num_pages=200, kept_layers=[0, 1, 2], mask_token_id=210)


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("toy_sdar"))
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    path = os.path.join(root, CONFIG)
    with open(path) as f:
        cfg = json.load(f)
    cfg.update(TOY, **TOY_SIZES, num_hidden_layers=3,
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


def _part(log, head, tail):
    return ast.literal_eval(log.split(head, 1)[1].split(tail, 1)[0].replace(
        "inf", "1e999").replace("nan", "1e999"))


def _state(log):
    return _part(log, "standing: served state ", "; kernel errors")


def _kernels(log):
    return _part(log, "; kernel errors ", "; checks ")


def _checks(log):
    return _part(log, "; checks ", "\n")


def _model(toy_root):
    return kanana_decode.builder(Registry(toy_root).config(NAME))


@pytest.mark.parametrize("trace", [0, 1])
def test_standing_bd_driver_at_toy_widths(toy_root, trace, capsys):
    out = run.run_cell(CELL, 2 ** 31 + 5, 0.25, trace, fluid.CPUPlace(),
                       root=toy_root)
    log = capsys.readouterr().out
    assert out["correct"] is True and out["failed"] == 0, log[-3000:]
    assert out["attempted"] == 3
    state, kernels, model = _state(log), _kernels(log), _model(toy_root)
    # every limit has its control's reading on its far side
    assert kernels["walk_decode"] <= model.PAGED_RTOL["walk_decode"] < kernels[
        "walk_decode_causal"]
    assert kernels["walk_chunk"] <= model.PAGED_RTOL["walk_chunk"] < kernels[
        "walk_chunk_causal"]
    assert state["state_mismatch"] == 0
    assert state["engine_kv_rows"] <= model.SERVED_STATE_TOL["kv_rows"] < state[
        "engine_kv_rows_8bit"]
    # rows that a denoising forward left (mask ids in the block) are far from
    # the whole block's: keeping them is a different result
    assert state["engine_kv_rows_deep"] <= model.SERVED_STATE_TOL[
        "kv_rows_deep"] < state["engine_kv_rows_stale"]
    for c in _checks(log):
        # (a toy's 211 ids and float32: the sound reading is reordering
        # alone, the shifted one past it ten-thousandfold)
        assert c["logit_err"] < 1e-4 * c["logits_shifted"]
        assert c["logit_err"] < model.LOGIT_TOL
        assert c["unmask_gap"] == 0 and c["block_as_served"] == 1.0
        # the ids the window served, under the reference's logits
        assert c["ids_agree"] == 1.0 and c["id_gap"] <= model.TIE_TOL
        assert c["routing"][0] == 1.0
        assert len(c["blocks"]) >= 2 and c["forwards"] >= 2 * 4
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
    assert {"diffusion_tokens_per_forward", "diffusion_kv_forward_share_pct",
            "diffusion_block_ms", "experts_touched_pct.sdar",
            "history_chunk_tokens_per_s", "decode_step_ms", "decode_wait_ms",
            "sched_iteration_ms", "sched_host_ms", "setup_warmup_s",
            "setup_trace_lower_s", "loop_compile_requests"} <= got
    # the toy's float32 logits over 211 ids stay under the threshold too: one
    # position a denoising forward, one forward in five writes K/V
    assert 0.75 < out["metrics"]["diffusion_tokens_per_forward"]["value"] <= 0.85
    assert 18 < out["metrics"]["diffusion_kv_forward_share_pct"]["value"] < 25
    assert out["metrics"]["diffusion_block_ms"]["value"] > 0
    assert not got & {"full_attn_decode_ms", "moe_expert_decode_ms",
                      "sdar_attn_roofline_pct", "moe_expert_roofline_pct.sdar",
                      "decode_hbm_mfu_pct.sdar"}


def _patched(M, monkeypatch, name, wrap):
    monkeypatch.setattr(M, name, wrap(getattr(M, name)))


def _eight_bit_rows(M, monkeypatch):
    """K and V rows kept in 8 bits: the precision below the
    configuration's."""
    def wrap(step):
        def rounded(*args, **kwargs):
            out = step(*args, **kwargs)
            cache = {name: leaf.astype("float8_e4m3fn").astype(leaf.dtype)
                     for name, leaf in out[1].items()}
            return (out[0], cache) + tuple(out[2:])
        return rounded

    for name in ("decode_step", "prefill_chunk"):
        _patched(M, monkeypatch, name, wrap)


def _causal_inside_the_block(M, monkeypatch):
    """The walk under the causal rule: the shortcut of a one-token decoder's
    kernel."""
    from paddle_tpu.parallel import flash_attention as FA

    for name in ("paged_gqa_decode_attention", "paged_gqa_prefill_attention"):
        _patched(FA, monkeypatch, name, lambda f: (
            lambda *a, block=1, **kw: f(*a, block=1, **kw)))


def _state_of_another_slot(M, monkeypatch):
    """The host reads every slot's block from the slot beside it: a fault of
    many live slots, which neither the replay nor the engine's own programs
    run over one slot can show."""
    from paddle_tpu.serving import decode_scheduler as DS

    def wrap(block_state):
        def rolled(vec, slots, block):
            ids, *rest = block_state(vec, slots, block)
            return (np.roll(ids, 1, axis=0), *rest)
        return rolled

    _patched(DS, monkeypatch, "block_state", wrap)


CONTROLS = {"eight_bit_rows": _eight_bit_rows,
            "causal_inside_the_block": _causal_inside_the_block,
            "state_of_another_slot": _state_of_another_slot}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_control_comes_out_not_correct(toy_root, monkeypatch, capsys,
                                         control):
    """Step programs that keep their rows in 8 bits, or attend causally
    inside a block, or a loop that hands a slot another slot's block, fail the
    cell by a limit that names them."""
    from paddle_tpu.models import sdar as M

    CONTROLS[control](M, monkeypatch)
    out = run.run_cell(CELL, 2 ** 31 + 9, 0.25, 0, fluid.CPUPlace(),
                       root=toy_root)
    assert out["correct"] is False
    log = capsys.readouterr().out
    model = _model(toy_root)
    if control == "state_of_another_slot":
        # the replay's programs and the engine's own are sound: only the
        # served ids, held to the reference, show it
        assert "NOT CORRECT: request" in log and "the ids the window served" in log
        assert min(c["ids_agree"] for c in _checks(log)) < model.IDS_AGREE
        assert max(c["logit_err"] for c in _checks(log)) < model.LOGIT_TOL
        assert _state(log)["state_mismatch"] == 0
        assert "NOT CORRECT: kernels" not in log
        return
    assert out["failed"] == 0
    if control == "eight_bit_rows":
        assert "NOT CORRECT: the engine's own programs on its own cache" in log
        assert _state(log)["engine_kv_rows"] > model.SERVED_STATE_TOL["kv_rows"]
        assert "NOT CORRECT: kernels" not in log
    else:
        assert "NOT CORRECT: kernels vs reference" in log
        assert max(c["logit_err"] for c in _checks(log)) > model.LOGIT_TOL


def test_the_replay_follows_the_served_trajectory(toy_root):
    """``follow_served``: the step program's set with the served ids seated;
    where the served id there is another answer and a masked position beside
    it was served its top, that one was taken first (a near-tie between
    positions, judged afterwards); with nothing to turn to, the id is seated
    for the judge to find."""
    model = _model(toy_root)
    cfg = dict(mask_token_id=9)
    ids = np.asarray([9, 3, 9, 9], np.int32)
    logits = np.zeros((4, 10))
    logits[0, 5], logits[2, 6], logits[3, 7] = 4.0, 3.9, 1.0
    rule = np.asarray([True, False, False, False])
    new, took = model.follow_served(cfg, ids, logits, rule,
                                    np.asarray([5, 3, 6, 7]))
    assert took == [0] and list(new) == [5, 3, 9, 9]
    # position 0 was served an id out of another forward: position 2, whose
    # confidence is as good as equal, went first
    new, took = model.follow_served(cfg, ids, logits, rule,
                                    np.asarray([1, 3, 6, 7]))
    assert took == [2] and list(new) == [9, 3, 6, 9]
    # ... but not a position far less confident: seated as served
    new, took = model.follow_served(cfg, ids, logits, rule,
                                    np.asarray([1, 3, 2, 7]))
    assert took == [0] and list(new) == [1, 3, 9, 9]
    # nothing the served ids agree with: seated as served
    new, took = model.follow_served(cfg, ids, logits, rule,
                                    np.asarray([1, 3, 2, 2]))
    assert took == [0] and list(new) == [1, 3, 9, 9]
    assert model.gap(logits[0], 1) > model.TIE_TOL


# -- the readers on hand-made observations ------------------------------------

def _observed(cfg, counters, **more):
    return dict({"window_counters": counters, "config": cfg,
                 "peak": lambda key: {"hbm_bytes_per_s": 819e9}[key]}, **more)


def test_byte_counts_follow_the_configuration():
    cfg = Registry(ROOT).config(NAME)
    # a layer's attention 18.87 M x 2 B and its router 0.26 M x 4 B, six times
    assert abs(sdar_decode.dense_bytes(cfg) - 6 * (37.75e6 + 1.05e6)) < 1e6
    # every expert touched: 6 x 128 x 4.72 M x 2 B = 7.25 GB
    assert abs(sdar_decode.expert_bytes(cfg, 6 * 128) - 7.247e9) < 5e6
    assert abs(sdar_decode.head_bytes(cfg, 64) - 0.6234e9) < 1e6
    # a token's K and V rows in one layer: 2 x 512 x 2 B
    assert sdar_decode.kv_row_bytes(cfg) == 2048
    assert sdar_decode.kv_read_bytes(cfg, 1000) == 2048000
    assert sdar_decode.kv_write_bytes(cfg, 10) == 2048 * 4 * 6 * 10
    # the issue's reckoning of a perfect forward at the window's start: 10 GB
    counts = dict(forwards=64, kv_forwards=12.8, experts_touched=6 * 128,
                  kv_rows_read=6 * 160000)
    assert 9.5e9 < sdar_decode.step_bytes(cfg, counts) < 10.5e9


def test_readers_on_hand_made_counters():
    cfg = Registry(ROOT).config(NAME)
    reg = Registry(ROOT)
    steps, live = 100, 64
    counters = {"serving.decode.steps": steps,
                "serving.decode.diffusion.forwards": steps * live,
                "serving.decode.diffusion.kv_forwards": steps * live // 5,
                "serving.decode.diffusion.unmasked": steps * live * 4 // 5,
                "serving.decode.diffusion.kv_rows_read": steps * 160000 * 6,
                "serving.decode.moe.experts_touched": steps * 6 * 120}
    obs = _observed(cfg, counters)
    read = {n: reg.module("layer_metrics", n).read for n in NEW_METRICS}
    assert read["diffusion_tokens_per_forward"](obs) == 0.8
    assert read["diffusion_kv_forward_share_pct"](obs) == 20.0
    assert abs(read["experts_touched_pct.sdar"](obs) - 93.75) < 1e-9
    for name in NEW_METRICS - {"diffusion_tokens_per_forward",
                               "diffusion_kv_forward_share_pct",
                               "experts_touched_pct.sdar"}:
        assert read[name](obs) is None          # no device trace, no span
    # a program without the counters (the parent): every reader is silent
    silent = _observed(cfg, {"serving.decode.steps": steps})
    assert all(read[n](silent) is None for n in NEW_METRICS)


# -- the configuration's own file ---------------------------------------------

def test_configuration_states_the_catalogs_row():
    cfg = Registry(ROOT).config(NAME)
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["hidden_size"],
            pub["num_attention_heads"], pub["num_key_value_heads"],
            pub["head_dim"], pub["intermediate_size"],
            pub["moe_intermediate_size"], pub["num_experts"],
            pub["num_experts_per_tok"], pub["vocab_size"], pub["rope_theta"],
            pub["rms_norm_eps"], pub["max_window_layers"]) == (
                48, 2048, 32, 4, 128, 6144, 768, 128, 8, 151936, 1000000,
                1e-6, 48)
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert {k for k in pub if cfg[k] != pub[k]} == {"num_hidden_layers"}
    assert cfg["num_hidden_layers"] == 6 and cfg["kept_layers"] == list(range(6))
    assert "eight-stage pipeline" in cfg["stands_for"]
    assert (cfg["block_length"], cfg["denoising_steps"],
            cfg["confidence_threshold"], cfg["mask_token_id"]) == (
                4, 4, 0.9, 151669)
    for key in ("block_length", "denoising_steps", "remasking",
                "confidence_threshold", "mask_token_id", "qk_norm", "no_bias",
                "unshifted_logits", "router_precision", "kv_write_policy"):
        assert cfg["assumed"][key]
    assert cfg["page"] % cfg["block_length"] == 0


def test_mix_reserves_what_the_configuration_holds():
    from chipbench import traffic

    reg = Registry(ROOT)
    cfg, mix = reg.config(NAME), reg.traffic(TRAFFIC)
    model = kanana_decode.builder(cfg)
    assert mix["requests"] == cfg["slots"] == 64
    reqs = [(model.prompt_ids(cfg, p), n) for p, n in traffic.requests(
        mix, mix["requests"], 2 ** 31 + 1, cfg["vocab_size"] - 1)]
    lens = sorted(len(p) for p, _ in reqs)
    assert (lens[0], lens[-1], sum(lens)) == (512, 8192, 155670)
    assert all(n == 2560 for _, n in reqs)
    assert max(lens) + 2560 <= cfg["max_seq_len"]
    reserved = sum(-(-(n + 2560) // cfg["page"]) for n in lens)
    assert reserved == 5025 < cfg["num_pages"]
    # ids over the vocabulary less the mask id; lengths as drawn, most not
    # whole blocks
    ids = np.concatenate([p for p, _ in reqs])
    assert 0 < ids.min() and ids.max() < cfg["vocab_size"]
    assert not (ids == cfg["mask_token_id"]).any()
    assert sum(n % cfg["block_length"] != 0 for n in lens) == 46
