"""The ``glm5_standing_dsactx`` cell on the CPU at toy widths: the standing
driver for a latent cache beside an indexer's key cache, a learned selection
and a share of routed experts (``drivers/serve_standing_dsa.py``), the model
builder (``models/glm_moe_dsa.py``), the plain reference and the ten per-layer
readers through ``run.run_cell`` on a toy checkout, at ``--trace 0`` and
``1``; the controls of the cell's precision limits (an 8-bit ``index_k`` leaf
and an 8-bit latent leaf each come out not correct, by the limit that names
them) and of the mechanism (a window in place of the selection); the contract
on that checkout; the readers on hand-made observations; and the
configuration's own file against the catalog's facts.  No test needs a chip."""
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
from chipbench import contract, glm5_decode, run  # noqa: E402
from chipbench.registry import Registry  # noqa: E402

CELL = "glm5_standing_dsactx"
NAME = "glm5_744b_a40b"
CONFIG = "chipbench/configs/%s.json" % NAME
# toy sizes in the family's own key names (index_topk 16 against contexts of
# 80 to 200); a toy is not the model, so its published block is cut with it
TOY = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
           vocab_size=96, num_attention_heads=4, num_key_value_heads=4,
           head_dim=8, kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
           qk_rope_head_dim=8, qk_head_dim=24, v_head_dim=16,
           index_n_heads=4, index_head_dim=16, index_topk=16,
           n_routed_experts=8, num_experts_per_tok=3, num_hidden_layers=3)
TOY_SIZES = dict(weights_dtype="float32", kv_dtype="float32", slots=4,
                 max_seq_len=1280, page=8, num_pages=641, chunk=16,
                 buckets=[8, 16, 200], kept_layers=[2, 3, 4],
                 router_experts=24, experts_held=[8, 16], vocab_held=[0, 96])
NEW_METRICS = {"dsa_index_decode_ms", "dsa_index_roofline_pct",
               "dsa_select_decode_ms", "mla_rows_decode_ms",
               "mla_rows_roofline_pct", "dsa_selected_share_pct",
               "moe_expert_roofline_pct.glm5", "experts_touched_pct.glm5",
               "moe_pairs_held_pct.glm5", "decode_hbm_mfu_pct.glm5"}


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("toy_glm5"))
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    path = os.path.join(root, CONFIG)
    with open(path) as f:
        cfg = json.load(f)
    published = dict(cfg["published"], **TOY)
    published.update(num_hidden_layers=78, n_routed_experts=24, vocab_size=768)
    cfg.update(TOY, **TOY_SIZES, published=published)
    with open(path, "w") as f:
        json.dump(cfg, f)
    path = os.path.join(root, "chipbench/traffic/standing_dsactx.json")
    with open(path) as f:
        mix = json.load(f)
    mix.update(requests=4, max_prompt=200, setup_limit_s=300, trace_s=0.3,
               prompt_len={"dist": "lognormal", "median": 110, "sigma": 0.5,
                           "min": 40, "max": 200},
               output_len={"dist": "constant", "value": 1000, "max": 1000})
    with open(path, "w") as f:
        json.dump(mix, f)
    return root


def test_toy_checkout_is_within_the_contract(toy_root):
    assert contract.violations(toy_root) == []


def _run(toy_root, seed, trace=0, seconds=0.25):
    import importlib.util

    # the registry loads a driver by path, once a Registry: patch the module
    # that THIS run's registry will load
    spec = importlib.util.spec_from_file_location(
        "toy_dsa_driver", os.path.join(
            toy_root, "chipbench/drivers/serve_standing_dsa.py"))
    driver = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(driver)
    driver.REFERENCE_PAD = (128, 256)
    was = Registry.module

    def module(self, kind, name):
        if (kind, name) == ("drivers", "serve_standing_dsa"):
            return driver
        return was(self, kind, name)

    Registry.module = module
    try:
        return run.run_cell(CELL, seed, seconds, trace, fluid.CPUPlace(),
                            root=toy_root)
    finally:
        Registry.module = was


@pytest.mark.parametrize("trace", [0, 1])
def test_standing_dsa_driver_at_toy_widths(toy_root, trace):
    out = _run(toy_root, 2 ** 31 + 5, trace)
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
    assert {"dsa_selected_share_pct", "experts_touched_pct.glm5",
            "moe_pairs_held_pct.glm5", "decode_step_ms", "decode_wait_ms",
            "sched_iteration_ms", "history_chunk_tokens_per_s",
            "setup_warmup_s"} <= got
    # 16 selected of contexts of 40 to 200 and what was written since
    assert 5 < out["metrics"]["dsa_selected_share_pct"]["value"] < 45
    assert 0 < out["metrics"]["moe_pairs_held_pct.glm5"]["value"] < 100
    assert not got & {"dsa_index_decode_ms", "dsa_select_decode_ms",
                      "mla_rows_decode_ms", "decode_hbm_mfu_pct.glm5"}


def _log_dict(log, after, before):
    return ast.literal_eval(log.split(after, 1)[1].split(before, 1)[0]
                            .replace("inf", "1e999").replace("nan", "1e999"))


def _rounding(leaf):
    """The step functions with ``leaf`` of the cache kept in 8 bits."""
    import jax

    from paddle_tpu.models import deepseek_v3 as M

    def eight_bit(step):
        def rounded(*args, **kwargs):
            out = step(*args, **kwargs)
            cache = dict(out[1])
            cache[leaf] = jax.lax.reduce_precision(cache[leaf], 4, 3)
            return (out[0], cache) + tuple(out[2:])
        return rounded

    return {name: eight_bit(getattr(M, name))
            for name in ("decode_step", "prefill_chunk")}


@pytest.mark.parametrize("leaf,limit", [("index_k", "index_rows"),
                                        ("latent", "latent_rows")])
def test_an_eight_bit_leaf_comes_out_not_correct(toy_root, monkeypatch, capsys,
                                                 leaf, limit):
    """The controls of ``SERVED_STATE_TOL``: either leaf kept in 8 bits (the
    precision below the 16 the configuration states) fails the cell through
    ``run_cell`` by the reading taken from the engine's own programs on its
    own cache, the one that names the leaf."""
    from paddle_tpu.models import deepseek_v3 as M

    for name, fn in _rounding(leaf).items():
        monkeypatch.setattr(M, name, fn)
    out = _run(toy_root, 2 ** 31 + 9)
    log = capsys.readouterr().out
    assert out["correct"] is False
    held = _log_dict(log, "standing: served state ", "; mechanism errors")
    model = Registry(toy_root).module("models", "glm_moe_dsa")
    assert held[limit] > model.SERVED_STATE_TOL[limit]
    other = "latent_rows" if limit == "index_rows" else "index_rows"
    assert held[other] <= model.SERVED_STATE_TOL[other]


def test_a_window_in_place_of_the_selection_comes_out_not_correct(
        toy_root, monkeypatch, capsys):
    """The control of the mechanism: a program that attends to the LAST
    ``index_topk`` tokens (what a sliding window would) is not the model,
    and the selection's own limits say so."""
    import jax.numpy as jnp

    from paddle_tpu.parallel import flash_attention as FA

    def last_k(scores, n_visible, k):
        pos = jnp.arange(scores.shape[1])[None, :]
        return (pos < n_visible[:, None]) & (pos >= n_visible[:, None] - k)

    monkeypatch.setattr(FA, "dsa_keep", last_k)
    out = _run(toy_root, 2 ** 31 + 11)
    log = capsys.readouterr().out
    assert out["correct"] is False
    assert "share of the reference's set held" in log
    model = Registry(toy_root).module("models", "glm_moe_dsa")
    for seen in _log_dict(log, "; selection ", "\n"):
        # under a half of the reference's set in every layer, and logits far
        # from the reference's on its own sets
        assert max(seen["set_held_min"]) < 0.5
        assert max(seen["own_sets_logit_err"]) > 2 * model.OWN_SETS_LOGIT_TOL


def test_readers_on_hand_made_observations():
    reg = Registry(ROOT)
    cfg = reg.config(NAME)
    P = "serving.decode."
    steps = 100
    observed = {
        "config": cfg, "peak": lambda key: 819e9,
        "window_counters": {
            P + "steps": steps, P + "index.rows_scored": steps * 5 * 400000,
            P + "sparse.visible_tokens": steps * 5 * 400000,
            P + "sparse.selected_tokens": steps * 5 * 24 * 2048,
            P + "moe.pairs": steps * 48, P + "moe.experts_touched": steps * 34,
            P + "moe.pairs_elsewhere": steps * (24 * 8 * 4 - 48)},
        "decode_stages": {"index": ["paged_index_scores.7"],
                          "select": ["while.3", "fusion.11"],
                          "rows": ["gather.2", "paged_mla_rows_attention.9"]},
        "busy_s": 1.0,
        "trace": {"planes": {"/device:TPU:0": {
            "XLA Modules": [["jit_decode(1)", 0, 10_000_000],
                            ["jit_decode(1)", 10_000_000, 10_000_000]],
            "XLA Ops": [
                ["paged_index_scores.7 custom-call f32[24,1,53248]", 0,
                 1_000_000],
                ["while.3 while (u32[24])", 1_000_000, 600_000],
                ["fusion.12 fusion s32[24]", 1_100_000, 200_000],
                ["fusion.11 fusion s32[24,2048]", 1_600_000, 200_000],
                ["gather.2 gather bf16[24,2048,640]", 2_000_000, 500_000],
                ["paged_mla_rows_attention.9 custom-call f32[24,64,512]",
                 2_500_000, 500_000],
                ["moe_grouped_matmul.4 custom-call f32[192,4096]", 3_000_000,
                 2_000_000],
                ["paged_index_scores.7 custom-call f32[24,1,53248]",
                 19_000_000, 1_000_000]]}}},
    }

    def read(name):
        return reg.module("layer_metrics", name).read(observed)

    assert read("dsa_index_decode_ms") == pytest.approx(1.0)
    # the loop's own time less its body's, plus the compaction: 0.4 + 0.2
    assert read("dsa_select_decode_ms") == pytest.approx(0.3)
    assert read("mla_rows_decode_ms") == pytest.approx(0.5)
    counts = glm5_decode.step_counts(observed)
    assert counts["selected"] == 5 * 24 * 2048
    assert read("dsa_index_roofline_pct") == pytest.approx(
        100 * 2e6 * 256 / 819e9 / 1e-3)
    assert read("mla_rows_roofline_pct") == pytest.approx(
        100 * counts["selected"] * 1152 / 819e9 / 0.5e-3)
    assert read("dsa_selected_share_pct") == pytest.approx(
        100 * 24 * 2048 / 400000)
    assert read("experts_touched_pct.glm5") == pytest.approx(100 * 34 / 64)
    assert read("moe_pairs_held_pct.glm5") == pytest.approx(6.25)
    assert read("moe_expert_roofline_pct.glm5") == pytest.approx(
        100 * 34 * 3 * 6144 * 2048 * 2 / 819e9 / 1e-3)
    want = glm5_decode.step_bytes(cfg, counts)
    assert read("decode_hbm_mfu_pct.glm5") == pytest.approx(
        100 * want / 819e9 / 10e-3)
    # 5.3 GB of weights touched, 0.5 GB of indexer keys, 0.28 GB of rows
    assert 5.0e9 < glm5_decode.weight_bytes(cfg) + glm5_decode.expert_bytes(
        cfg, 34) < 5.7e9
    # a program with no indexer, no text or no trace: every reader is silent
    for less in ({"window_counters": {}}, {"decode_stages": None}):
        quiet = dict(observed, **less)
        for name in NEW_METRICS:
            got = reg.module("layer_metrics", name).read(quiet)
            if "decode_stages" in less and name in (
                    "dsa_index_decode_ms", "dsa_select_decode_ms",
                    "mla_rows_decode_ms", "dsa_index_roofline_pct",
                    "mla_rows_roofline_pct"):
                assert got is None, name
            if "window_counters" in less and name not in (
                    "dsa_index_decode_ms", "dsa_select_decode_ms",
                    "mla_rows_decode_ms"):
                assert got is None, name


def test_stage_names_come_from_the_programs_own_scopes():
    text = "\n".join([
        'ENTRY %main {',
        '  %fusion.3 = f32[24]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(decode)/jit(main)/mla_attention/dsa_select/while/'
        'body/ge" source_file="x.py"}',
        '  ROOT %paged_index_scores.7 = f32[24,1,53248]{2,1,0} custom-call('
        '%a), metadata={op_name="jit(decode)/jit(main)/mla_attention/'
        'dsa_index/pallas_call"}',
        '  %gather.2 = bf16[24,2048,640] gather(%x), metadata={op_name='
        '"jit(decode)/jit(main)/mla_attention/mla_rows/gather"}',
        '  %dot.1 = f32[24,64] dot(%x), metadata={op_name="jit(decode)/'
        'jit(main)/mla_attention/dot_general"}', '}'])
    assert glm5_decode.stage_names(text) == {
        "index": ["paged_index_scores.7"], "select": ["fusion.3"],
        "rows": ["gather.2"]}


def test_the_configuration_is_the_catalogs_row_cut_as_it_says():
    with open(os.path.join(ROOT, CONFIG)) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    pub = cfg["published"]
    assert (pub["hidden_size"], pub["num_hidden_layers"], pub["q_lora_rank"],
            pub["index_topk"], pub["n_routed_experts"]) == (
                6144, 78, 2048, 2048, 256)
    for key, value in pub.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["experts_held"] == [0, 16] and cfg["router_experts"] == 256
    assert cfg["vocab_held"] == [0, cfg["vocab_size"]]
    assert cfg["vocab_size"] * 8 == pub["vocab_size"]
    assert cfg["kept_layers"] == [2, 3, 4, 5, 6]
    assert "EP16" in cfg["stands_for"] and "0.75 rows" in cfg["stands_for"]
    mix = Registry(ROOT).traffic("standing_dsactx")
    assert mix["requests"] == cfg["slots"] == 24
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 12288,
                                 "sigma": 0.6, "min": 4096, "max": 49152}
    assert mix["prompt_len"]["min"] >= 2 * cfg["index_topk"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert NEW_METRICS <= listed
