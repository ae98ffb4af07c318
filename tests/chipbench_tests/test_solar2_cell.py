"""The standing driver for a model with a delta-rule state (``drivers/
serve_standing_kda.py``), its model builder (``models/solar_open2.py``), the
plain reference and the eight new per-layer readers through ``run.run_cell`` on
a toy checkout at toy widths on the CPU, at ``--trace 0`` and ``1``; the
controls of the cell's limits (a bfloat16 state, ``beta`` without its factor 2,
one decay a head, 3 taps, no output gate, rotary in the softmax layer, a
bfloat16 router each read past the limit that names them, and a served program
that IS the control comes out not correct); the contract on that checkout; the
readers on hand-made observations; the byte counts; and the configuration's
own file against the catalog's row.  No test needs a chip."""
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

NEW_METRICS = {"kda_state_decode_ms", "kda_state_roofline_pct",
               "gqa_attn_roofline_pct.solar", "moe_expert_roofline_pct.solar",
               "experts_touched_pct.solar", "expert_load_max_over_mean.solar",
               "moe_pairs_held_pct.solar", "decode_hbm_mfu_pct.solar"}
_BENCH = Registry(ROOT).bench
CELL = next(m for m in _BENCH["per_layer"]
            if m["name"] == "kda_state_roofline_pct")["workloads"][0]
_ENTRY = next(w for w in _BENCH["workloads"] if w["name"] == CELL)
NAME, TRAFFIC = _ENTRY["config"], _ENTRY["traffic"]
CONFIG = next(c for c in _BENCH["configs"] if c["name"] == NAME)["file"]
# toy sizes in the family's own key names; a toy is not the model, so its
# published block is cut with it
TOY = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           num_experts_per_tok=4, max_position_embeddings=2048,
           linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16,
                               "num_heads": 4, "num_kv_heads": None})
TOY_SIZES = dict(weights_dtype="float32", kv_dtype="float32",
                 conv_state_dtype="float32", kda_gate_rank=8, slots=4,
                 max_seq_len=1120, page=8, chunk=32, buckets=[8, 32, 112],
                 num_pages=561, kept_layers=[0, 1, 2, 3],
                 router_experts=16, experts_held=[0, 4], vocab_held=[0, 96])


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("toy_solar"))
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    path = os.path.join(root, CONFIG)
    with open(path) as f:
        cfg = json.load(f)
    published = dict(cfg["published"], **TOY)
    published.update(n_routed_experts=16, vocab_size=768)
    cfg.update(TOY, **TOY_SIZES, published=published, n_routed_experts=4,
               vocab_size=96)
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


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Two blocks of the chunk-wise form inside a toy chunk of 32."""
    from paddle_tpu.parallel import kda

    monkeypatch.setattr(kda, "KDA_BLOCK", 16)


def test_toy_checkout_is_within_the_contract(toy_root):
    assert contract.violations(toy_root) == []


def _held(log):
    return ast.literal_eval(log.split("standing: served state ", 1)[1].split(
        "; mechanism errors", 1)[0].replace("inf", "1e999"))


def _mechanisms(log):
    return ast.literal_eval(log.split("; mechanism errors ", 1)[1].split(
        "; checks", 1)[0].replace("inf", "1e999"))


def _model(toy_root):
    return kanana_decode.builder(Registry(toy_root).config(NAME))


@pytest.mark.parametrize("trace", [0, 1])
def test_standing_kda_driver_at_toy_widths(toy_root, trace, capsys):
    # a window short enough that no request reaches its 1000th token
    out = run.run_cell(CELL, 2 ** 31 + 5, 0.25, trace, fluid.CPUPlace(),
                       root=toy_root)
    log = capsys.readouterr().out
    assert out["correct"] is True and out["failed"] == 0, log[-3000:]
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
        # every control reads past the limit the sound reading is under
        errs, model = _mechanisms(log), _model(toy_root)
        for name, limit in model.MECHANISM_RTOL.items():
            assert errs[name] <= limit, (name, errs)
        assert errs["kda_decode_bf16_state"] > 2 * model.MECHANISM_RTOL[
            "kda_decode"]
        assert errs["routing_mismatch_bf16"] > model.MECHANISM_RTOL[
            "routing_mismatch"]
        for v in model.KDA_VARIANTS:
            assert errs["kda_layer_decode_" + v] > 2 * model.MECHANISM_RTOL[
                "kda_layer_decode"], (v, errs)
        for v in model.GQA_VARIANTS:
            assert errs["gqa_layer_decode_" + v] > 2 * model.MECHANISM_RTOL[
                "gqa_layer_decode"], (v, errs)
        held = _held(log)
        assert held["kv_rows_rotary"] > 5 * model.SERVED_STATE_TOL["kv_rows"]
        for name, limit in model.SERVED_STATE_TOL.items():
            assert held[name] <= limit, (name, held)
        return
    # no device in a CPU trace: the device readers leave their metrics out,
    # the counter and span readers report
    got = set(out["metrics"])
    assert {"experts_touched_pct.solar", "expert_load_max_over_mean.solar",
            "moe_pairs_held_pct.solar", "history_prefill_tokens_per_s",
            "decode_step_ms", "decode_wait_ms", "sched_iteration_ms",
            "sched_host_ms", "setup_warmup_s"} <= got
    assert 0 < out["metrics"]["experts_touched_pct.solar"]["value"] <= 100
    assert out["metrics"]["expert_load_max_over_mean.solar"]["value"] >= 1
    # 4 of 16 experts are held: a quarter of the pairs, give or take
    assert 5 < out["metrics"]["moe_pairs_held_pct.solar"]["value"] < 60
    assert not got & {"kda_state_decode_ms", "kda_state_roofline_pct",
                      "moe_expert_decode_ms", "decode_hbm_mfu_pct.solar"}


def _wrong(monkeypatch, what):
    """Make the served program the control ``what``."""
    import jax.numpy as jnp

    from paddle_tpu.models import solar_open2 as M
    from paddle_tpu.parallel import kda

    if what == "bf16_state":
        real_step, real_chunk = kda.kda_state_decode, kda.kda_chunk

        def coarse(x):
            return x.astype(jnp.bfloat16).astype(jnp.float32)

        monkeypatch.setattr(kda, "kda_state_decode", lambda *a, **k: (
            lambda o, s: (o, coarse(s)))(*real_step(*a, **k)))
        monkeypatch.setattr(kda, "kda_chunk", lambda *a, **k: (
            lambda o, s: (o, coarse(s)))(*real_chunk(*a, **k)))
        return
    real_dims = M._dims
    real_in, real_out = M._kda_in, M._kda_out
    if what == "beta1":
        monkeypatch.setattr(M, "_dims", lambda cfg: dict(real_dims(cfg),
                                                         beta=1.0))
    elif what == "head_decay":
        def one_decay(*a, **k):
            xp, g, beta, gate = real_in(*a, **k)
            return xp, jnp.broadcast_to(g.mean(-1, keepdims=True),
                                        g.shape), beta, gate
        monkeypatch.setattr(M, "_kda_in", one_decay)
    elif what == "no_gate":
        monkeypatch.setattr(M, "_kda_out", lambda d, lp, x, o, gate:
                            real_out(d, lp, x, o, jnp.ones_like(gate)))
    elif what == "taps3":
        # the oldest tap dropped, in the chunk and in the step
        for name in ("_kda_chunk_layer", "_kda_decode_layer"):
            monkeypatch.setattr(M, name, lambda d, p, lp, *a, _real=getattr(
                M, name): _real(d, p, dict(lp, conv_w=lp["conv_w"].at[0].set(
                    0.0)), *a))
    elif what in ("rotary", "gqa_no_gate"):
        real_gqa_in, at = M._gqa_in, {}

        def rope(x, positions, theta):
            half = x.shape[-1] // 2
            ang = positions.astype(jnp.float32)[:, None, None] * theta ** (
                -jnp.arange(half, dtype=jnp.float32) / half)
            x1, x2 = x[..., :half], x[..., half:]
            return jnp.concatenate(
                [x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                 x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)

        def gqa_in(d, p, lp, layer, x):
            q, k, v, gate = real_gqa_in(d, p, lp, layer, x)
            if what == "gqa_no_gate":
                return q, k, v, jnp.ones_like(gate)
            k = rope(k.reshape(-1, d["Hkv"], d["Dh"]), at["positions"], 1e4)
            return (rope(q, at["positions"], 1e4).astype(q.dtype),
                    k.reshape(v.shape), v, gate)

        real_chunk, real_step = M._gqa_chunk_layer, M._gqa_decode_layer

        def chunk(d, p, lp, layer, x, cache, chunk_pages, gather_pages, start,
                  valid):
            at["positions"] = start + jnp.arange(x.shape[0])
            return real_chunk(d, p, lp, layer, x, cache, chunk_pages,
                              gather_pages, start, valid)

        def step(d, p, lp, layer, x, cache, page_tables, kv_lens, *rest):
            at["positions"] = jnp.maximum(kv_lens - 1, 0)
            return real_step(d, p, lp, layer, x, cache, page_tables, kv_lens,
                             *rest)

        monkeypatch.setattr(M, "_gqa_in", gqa_in)
        monkeypatch.setattr(M, "_gqa_chunk_layer", chunk)
        monkeypatch.setattr(M, "_gqa_decode_layer", step)
    else:
        raise AssertionError(what)


@pytest.mark.parametrize("what,reading", [
    ("bf16_state", "kda_decode"), ("beta1", "kda_layer_decode"),
    ("head_decay", "kda_layer_decode"), ("no_gate", "kda_layer_decode"),
    ("taps3", "kda_layer_decode"), ("rotary", "gqa_layer_decode"),
    ("gqa_no_gate", "gqa_layer_decode")])
def test_a_served_control_comes_out_not_correct(toy_root, monkeypatch, capsys,
                                                what, reading):
    """A served program that IS the control fails the cell by the mechanism
    limit that names it."""
    _wrong(monkeypatch, what)
    out = run.run_cell(CELL, 2 ** 31 + 9, 0.25, 0, fluid.CPUPlace(),
                       root=toy_root)
    assert out["correct"] is False and out["failed"] == 0
    log = capsys.readouterr().out
    assert "NOT CORRECT: mechanisms vs reference" in log
    errs, model = _mechanisms(log), _model(toy_root)
    assert errs[reading] > 2 * model.MECHANISM_RTOL[reading], errs


def test_bfloat16_router_scores_come_out_not_correct(toy_root, monkeypatch,
                                                     capsys):
    """The control of ``routing_mismatch``: a router whose logits come from
    bfloat16 operands fails the cell by that limit."""
    from paddle_tpu.parallel import moe

    model = _model(toy_root)

    def coarse(x, w, bias, *, top_k, scale=1.0, **kw):
        import jax
        import jax.numpy as jnp

        experts = model._route_bf16(x, w, bias, top_k)
        weights = jnp.take_along_axis(jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), w)), experts, axis=-1)
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
        return experts.astype(jnp.int32), weights * scale

    monkeypatch.setattr(moe, "route_topk", coarse)
    out = run.run_cell(CELL, 2 ** 31 + 9, 0.25, 0, fluid.CPUPlace(),
                       root=toy_root)
    assert out["correct"] is False and out["failed"] == 0
    log = capsys.readouterr().out
    assert "NOT CORRECT: mechanisms vs reference" in log
    errs = _mechanisms(log)
    assert errs["routing_mismatch"] > model.MECHANISM_RTOL["routing_mismatch"]


def test_a_chunk_that_drops_the_carry_comes_out_not_correct(
        toy_root, monkeypatch, capsys):
    """The control of ``kda_state`` / ``conv_state``: a chunk program that
    takes EVERY chunk's leaves as zero (no carry from chunk to chunk) leaves a
    state that knows the last few tokens only."""
    import jax.numpy as jnp

    from paddle_tpu.models import solar_open2 as M

    real = M._kda_chunk_layer
    monkeypatch.setattr(M, "_kda_chunk_layer", lambda d, p, lp, layer, x,
                        cache, slot, fresh, valid: real(
                            d, p, lp, layer, x, cache, slot,
                            jnp.asarray(True), valid))
    out = run.run_cell(CELL, 2 ** 31 + 9, 0.25, 0, fluid.CPUPlace(),
                       root=toy_root)
    assert out["correct"] is False and out["failed"] == 0
    log = capsys.readouterr().out
    assert "NOT CORRECT: the engine's own programs on its own cache" in log
    held, model = _held(log), _model(toy_root)
    assert held["kda_state"] > 2 * model.SERVED_STATE_TOL["kda_state"]
    assert held["conv_state"] > 2 * model.SERVED_STATE_TOL["conv_state"]


def _observed(config, **more):
    base = {"config": config, "peak": lambda key: 819e9,
            "window_counters": {
                "serving.decode.steps": 10,
                "serving.decode.kda.slot_updates": 10 * 3 * 4,
                "serving.decode.kv.full_tokens_read": 10 * 1 * 400,
                "serving.decode.moe.pairs": 10 * 4 * 5,
                "serving.decode.moe.experts_touched": 10 * 4 * 3,
                "serving.decode.moe.max_load": 10 * 4 * 2,
                "serving.decode.moe.pairs_elsewhere": 10 * 4 * 11},
            "active_slots": 4}
    base.update(more)
    return base


def _trace():
    """A hand-made trace of two decode steps: per step one softmax-layer walk
    of 100 us, three state updates of 40 us, eight grouped products of 50 us
    and a matmul of 380 us."""
    ops, mods, t = [], [], 1000
    for _ in range(2):
        mods.append(["jit_decode(123)", t, 1000_000])
        calls = [("paged_gqa_full_attention.1 custom-call f32[4,32,16]",
                  100_000)]
        calls += [("kda_state_decode.%d custom-call f32[4,4,16]" % i, 40_000)
                  for i in range(3)]
        calls += [("moe_grouped_matmul.%d custom-call f32[16,64]" % i, 50_000)
                  for i in range(8)]
        for name, dur in calls + [("fusion.3 fusion bf16[4,64]", 380_000)]:
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

    assert read("kda_state_decode_ms") == pytest.approx(0.12)
    assert read("full_attn_decode_ms") == pytest.approx(0.1)
    assert read("moe_expert_decode_ms") == pytest.approx(0.4)
    model = kanana_decode.builder(cfg)
    state = model.state_bytes(cfg, 12)
    assert state == 12 * 2 * 4 * 4 * 16 * 16
    kv = model.kv_bytes(cfg, 400)
    assert kv == 400 * 2 * 32 * 4
    experts = model.expert_bytes(cfg, 12)
    assert experts == 4 * 3 * 64 * 32 * 12
    assert read("kda_state_roofline_pct") == pytest.approx(
        100 * state / 819e9 / 0.12e-3)
    assert read("gqa_attn_roofline_pct.solar") == pytest.approx(
        100 * kv / 819e9 / 0.1e-3)
    assert read("moe_expert_roofline_pct.solar") == pytest.approx(
        100 * experts / 819e9 / 0.4e-3)
    counts = {"slot_updates": 12, "full_tokens": 400, "experts_touched": 12}
    assert read("decode_hbm_mfu_pct.solar") == pytest.approx(
        100 * model.step_bytes(cfg, counts) / 819e9 / 1e-3)
    assert model.step_bytes(cfg, counts) == (
        model.weight_bytes(cfg) + experts + kv + state
        + model.conv_bytes(cfg, 12))
    assert read("experts_touched_pct.solar") == pytest.approx(
        100 * 12 / (4 * 4))
    assert read("expert_load_max_over_mean.solar") == pytest.approx(
        8 / (20 / 4))
    assert read("moe_pairs_held_pct.solar") == pytest.approx(100 * 20 / 64)


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
    gqa, kda = model.mixer_params(cfg)
    expert = model.expert_params(cfg)
    assert expert == 15728640
    assert round(gqa / 1e6, 1) == 109.1 and round(kda / 1e6, 1) == 137.6
    L, D = cfg["num_hidden_layers"], cfg["hidden_size"]
    router = D * cfg["router_experts"]
    assert round(router / 1e6, 2) == 1.31
    # the share as the configuration's file states it: 3.308 B, 6.62 GB
    total = (gqa + 3 * kda + L * (expert + router)
             + L * cfg["n_routed_experts"] * expert + 2 * D * cfg["vocab_size"])
    assert round(total / 1e9, 3) == 3.308
    assert model.expert_bytes(cfg, 7) == 2 * 7 * expert
    assert model.kv_bytes(cfg, 11) == 11 * 4096
    assert model.state_bytes(cfg, 3 * 128) == 3 * 128 * 2 * 4 * 64 * 128 * 128
    assert model.conv_bytes(cfg, 1) == 4 * 24576 * 2
    # the leaves the cache allocates, as the file reckons them
    assert cfg["num_pages"] * cfg["page"] * 4096 == 4410834944
    assert cfg["slots"] * 3 * 64 * 128 * 128 * 4 == 1610612736


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
    assert cfg["reduced"] == ["num_hidden_layers", "gqa_layers",
                              "n_routed_experts", "vocab_size"]
    for key in set(pub) - set(cfg["reduced"]):
        assert cfg[key] == pub[key], key
    n = cfg["num_hidden_layers"]
    assert cfg["kept_layers"] == list(range(n)) and n == 4
    assert cfg["gqa_layers"] == [i for i in pub["gqa_layers"] if i < n]
    lo, hi = cfg["experts_held"]
    assert hi - lo == cfg["n_routed_experts"] == pub["n_routed_experts"] // 8
    assert cfg["router_experts"] == pub["n_routed_experts"]
    assert cfg["vocab_size"] * 8 == pub["vocab_size"]
    assert "EP8 x 12" in cfg["stands_for"] and "25.6" in cfg["stands_for"]
