"""The standing driver for an EVA cache (``drivers/serve_standing_eva.py``), its
model builder (``models/evabyte.py``), the plain reference and the five
per-layer readers through ``run.run_cell`` on a toy checkout at toy widths on
the CPU, at ``--trace 0`` and ``1``; the controls of the cell's limits (an
8-bit K/V row, summaries pooled in bfloat16, the two lists' softmaxes taken
apart each come out past a limit that names them); the contract on that
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
from chipbench import contract, eva_decode, kanana_decode, run  # noqa: E402
from chipbench.registry import Registry  # noqa: E402

NEW_METRICS = {"eva_attn_decode_ms", "eva_attn_roofline_pct",
               "eva_summary_row_share_pct", "eva_windows_closed_per_s",
               "decode_hbm_mfu_pct.evabyte"}
_BENCH = Registry(ROOT).bench
CELL = next(m for m in _BENCH["per_layer"]
            if m["name"] == "eva_summary_row_share_pct")["workloads"][0]
_ENTRY = next(w for w in _BENCH["workloads"] if w["name"] == CELL)
NAME, TRAFFIC = _ENTRY["config"], _ENTRY["traffic"]
CONFIG = next(c for c in _BENCH["configs"] if c["name"] == NAME)["file"]
# toy sizes in the family's own key names; a toy is not the model, so its
# published block is cut with it
TOY = dict(hidden_size=64, intermediate_size=96, num_attention_heads=2,
           num_key_value_heads=2, window_size=32, chunk_size=4,
           max_position_embeddings=2048, max_seq_length=2048)
TOY_SIZES = dict(weights_dtype="float32", kv_dtype="float32", slots=3,
                 max_seq_len=2048, page=8, summary_page_rows=4, chunk=16,
                 buckets=[8, 16, 2048], kept_layers=[0, 1],
                 num_pages={"summary": 385, "window": 16})


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("toy_evabyte"))
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    path = os.path.join(root, CONFIG)
    with open(path) as f:
        cfg = json.load(f)
    cfg.update(TOY, **TOY_SIZES, num_hidden_layers=2,
               published=dict(cfg["published"], **TOY))
    with open(path, "w") as f:
        json.dump(cfg, f)
    path = os.path.join(root, "chipbench/traffic/%s.json" % TRAFFIC)
    with open(path) as f:
        mix = json.load(f)
    mix.update(requests=3, max_prompt=112, setup_limit_s=300, trace_s=0.3,
               prompt_len={"dist": "lognormal", "median": 90, "sigma": 0.2,
                           "min": 72, "max": 112},
               output_len={"dist": "constant", "value": 1900, "max": 1900})
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


def _mechanisms(log):
    return ast.literal_eval(log.split("; mechanism errors ", 1)[1].split(
        "; checks", 1)[0].replace("inf", "1e999"))


def _model(toy_root):
    return kanana_decode.builder(Registry(toy_root).config(NAME))


@pytest.mark.parametrize("trace", [0, 1])
def test_standing_eva_driver_at_toy_widths(toy_root, trace, capsys):
    out = run.run_cell(CELL, 2 ** 31 + 5, 0.25, trace, fluid.CPUPlace(),
                       root=toy_root)
    log = capsys.readouterr().out
    assert out["correct"] is True and out["failed"] == 0, log[-3000:]
    assert out["attempted"] == 3
    held, errs, model = _held(log), _mechanisms(log), _model(toy_root)
    # the check's own schedule closed a window and read summaries that decode
    # steps had written
    assert held["windows_crossed"] >= 1 and held["summaries_by_decode"] >= 2
    # the controls read beside the sound path lie past the limits
    assert held["kv_rows_8bit"] > 2 * model.SERVED_STATE_TOL["kv_rows"]
    assert held["summary_pooling"] <= model.SERVED_STATE_TOL[
        "summary_pooling"] < held["summary_pooling_bf16"]
    assert errs["eva_decode_apart"] > 10 * model.MECHANISM_RTOL["eva_decode"]
    assert errs["eva_decode_one_summary_short"] > 10 * model.MECHANISM_RTOL[
        "eva_decode"]
    # every limit has a reading of the precision below on its far side
    for name in ("eva_decode", "eva_prefill"):
        assert errs[name] <= model.MECHANISM_RTOL[name] < errs[
            name + "_bf16_probabilities"]
    assert errs["summarise"] <= model.MECHANISM_RTOL["summarise"] < errs[
        "summarise_bf16"]
    assert held["summary_rows"] <= model.SERVED_STATE_TOL[
        "summary_rows"] < held["summary_rows_8bit"]
    assert held["kv_rows_deep_max"] < model.DEEP_ROW_TOL < held[
        "kv_rows_deep_8bit_min"]
    # (at the toy's two layers the 8-bit logits sit about at the limit the
    # cell's eight layers set; its own float32 rows read a thousand times less)
    checks = ast.literal_eval(log.split("; checks ", 1)[1].split("\n", 1)[0])
    sound = max(e for c in checks for e in c["logit_err"])
    assert held["logits_8bit_rows"] > 100 * sound > 0
    assert np.isfinite(held["summary_rows_bf16_pooling"])
    assert "standing: the loop's stalls over the process: {" in log
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
    assert {"eva_summary_row_share_pct", "eva_windows_closed_per_s",
            "history_chunk_tokens_per_s", "decode_step_ms", "decode_wait_ms",
            "sched_iteration_ms", "sched_host_ms", "setup_warmup_s"} <= got
    # contexts of 72..112 and more against a window of 32: 16 or 24 summaries
    # beside 1..32 window rows
    assert 20 < out["metrics"]["eva_summary_row_share_pct"]["value"] < 95
    assert not got & {"eva_attn_decode_ms", "eva_attn_roofline_pct",
                      "decode_hbm_mfu_pct.evabyte"}


def test_an_eight_bit_row_comes_out_not_correct(toy_root, monkeypatch, capsys):
    """The control of ``SERVED_STATE_TOL``'s ``kv_rows``: K, V and summary
    rows kept in 8 bits (the precision below the 16 the configuration states)
    fail the cell by the readings taken from the engine's own programs on its
    own cache; the stand-alone mechanisms, which bring their own pools, do
    not see it."""
    import jax.numpy as jnp

    from paddle_tpu.models import evabyte as M

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
    held, model = _held(log), _model(toy_root)
    for name in ("kv_rows", "summary_rows"):
        assert held[name] > 2 * model.SERVED_STATE_TOL[name]


def test_summaries_pooled_in_bfloat16_come_out_not_correct(toy_root,
                                                           monkeypatch,
                                                           capsys):
    """The control of ``summary_rows``: step programs that pool a chunk's
    rows in bfloat16 leave summary rows that the reference's are not; the K
    and V rows themselves still hold."""
    from paddle_tpu.models import evabyte as M

    model = _model(toy_root)
    monkeypatch.setattr(M, "summarise", lambda k, v, phi, mu: tuple(
        a.astype(k.dtype) for a in model._summarise_bf16(k, v, phi, mu)))
    out = run.run_cell(CELL, 2 ** 31 + 9, 0.25, 0, fluid.CPUPlace(),
                       root=toy_root)
    assert out["correct"] is False and out["failed"] == 0
    held = _held(capsys.readouterr().out)
    assert held["summary_pooling"] > model.SERVED_STATE_TOL["summary_pooling"]
    assert held["kv_rows"] <= model.SERVED_STATE_TOL["kv_rows"]


def _observed(config, **more):
    base = {"config": config, "peak": lambda key: 819e9, "seconds": 2.0,
            "window_counters": {
                "serving.decode.steps": 10,
                "serving.decode.eva.window_rows_read": 10 * 3 * 20,
                "serving.decode.eva.summary_rows_read": 10 * 3 * 16,
                "serving.decode.eva.chunks_summarised": 10 * 3 // 4,
                "serving.decode.eva.windows_closed": 3},
            "active_slots": 3}
    base.update(more)
    return base


def _trace():
    """A hand-made trace of two decode steps: per step two EVA custom calls
    of 100 us and a matmul of 300 us."""
    ops, mods, t = [], [], 1000
    for _ in range(2):
        mods.append(["jit_decode(123)", t, 500_000])
        for name, dur in [
                ("eva_window_summary_decode.%d custom-call f32[3,1,64]" % i,
                 100_000) for i in range(2)] + [
                     ("fusion.3 fusion bf16[3,64]", 300_000)]:
            ops.append([name, t, dur])
            t += dur
        t += 500_000
    return {"planes": {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods}}}


def test_device_readers_on_a_hand_made_trace(toy_root):
    reg = Registry(toy_root)
    cfg = reg.config(NAME)
    obs = _observed(cfg, trace=_trace(), busy_s=1e-3, traced_window_s=2e-3)

    def read(name):
        return reg.module("layer_metrics", name).read(obs)

    counts = eva_decode.step_counts(obs)
    assert counts == {"window_rows": 60, "summary_rows": 48, "chunks": 0.7,
                      "windows": 0.3}
    assert eva_decode.row_bytes(cfg) == 2 * 64 * 4
    attn = eva_decode.attention_bytes(cfg, counts)
    assert attn == 108 * 512 * 2
    assert read("eva_attn_decode_ms") == pytest.approx(0.2)
    assert read("eva_attn_roofline_pct") == pytest.approx(
        100 * attn / 819e9 / 0.2e-3)
    assert read("eva_summary_row_share_pct") == pytest.approx(100 * 48 / 108)
    assert read("eva_windows_closed_per_s") == pytest.approx(1.5)
    assert read("decode_hbm_mfu_pct.evabyte") == pytest.approx(
        100 * (eva_decode.weight_bytes(cfg) + attn
               + eva_decode.written_bytes(cfg, counts)) / 819e9 / 0.5e-3)


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
    D, F, L = cfg["hidden_size"], cfg["intermediate_size"], 8
    layer = 4 * D * D + 3 * D * F
    assert layer == 202375168 and cfg["num_hidden_layers"] == L
    assert eva_decode.weight_bytes(cfg) == 2 * (L * layer + 24 * D) + 4 * D * 2560
    assert eva_decode.row_bytes(cfg) == 16384
    counts = {"window_rows": 24 * 1024, "summary_rows": 24 * 896,
              "chunks": 1.5, "windows": 0.01}
    assert eva_decode.attention_bytes(cfg, counts) == 24 * 1920 * 16384 * 8
    assert eva_decode.written_bytes(cfg, counts) == 1.5 * 33 * 16384 * 8
    # the two groups' pages as the file reckons them: a page of 64 rows of
    # 4096 values, both leaves, 8 layers
    page = 64 * 4096 * 2 * 2 * L
    pages = cfg["num_pages"]
    assert pages["window"] == cfg["slots"] * (
        cfg["window_size"] // cfg["page"] + 1) + 1
    assert (pages["window"] + pages["summary"]) * page == 10687086592


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
    assert cfg["reduced"] == ["num_hidden_layers"]
    for key in set(pub) - set(cfg["reduced"]):
        assert cfg[key] == pub[key], key
    assert cfg["kept_layers"] == list(range(cfg["num_hidden_layers"]))
    assert (pub["num_hidden_layers"], cfg["num_hidden_layers"]) == (32, 8)
    assert "multibyte" in cfg["not_built"]
    assert {"summary_pooling", "window_aligned", "precision"} <= set(
        cfg["assumed"])
