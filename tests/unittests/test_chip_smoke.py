"""CPU rehearsal of chip_smoke.py: the test calls the phase functions at toy
widths (the script's ``main`` is what refuses a CPU), so the smoke's control
flow and checks stay exercised by tier-1 without a fallback in the script."""
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as fluid

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
import chip_smoke  # noqa: E402

TOY = dict(n_layer=2, n_head=2, d_model=32, d_inner=64, vocab=64)


def test_train_phase_at_toy_widths():
    rep = chip_smoke.phase_train(
        dict(TOY, batch=4, seq=16, dropout=0.1, lr=5e-3, steps=8,
             prefetched=4), fluid.CPUPlace(), seed=0)
    assert len(rep["losses"]) == 8 and rep["losses"][-1] < rep["losses"][0]
    # what main() asserts on the chip is false here, visibly: the kernels
    # ran interpreted on the CPU backend
    assert rep["platforms"] == ["cpu"] and rep["kernel_calls"] == 0


def test_serve_phase_at_toy_widths():
    sz = dict(TOY, slots=4, max_seq_len=128, page=16, chunk=32,
              buckets=(16, 32, 128), new_tokens=8,
              prompt_lens=(5, 70, 20, 40, 33, 36), shared_prefix=32)
    assert chip_smoke.serve_lowers_to_kernels(sz) == {"decode": 0,
                                                      "prefill": 0}
    rep = chip_smoke.phase_serve(sz, seed=0)
    assert rep["exact"] + len(rep["ties"]) == 6
    assert all(len(t) == 8 for t in rep["tokens"])


def test_near_tie_gap_is_in_logit_standard_deviations():
    sz = dict(TOY, slots=4, max_seq_len=64, page=16, chunk=32,
              buckets=(16, 32, 64), new_tokens=4, prompt_lens=(5,),
              shared_prefix=0)
    params, _ = chip_smoke._decode_model(sz, 0)
    prompt = chip_smoke._prompts(sz, 0)[0]
    logits = chip_smoke._reference_logits(params, sz, prompt,
                                          np.array([3], np.int32))
    assert logits.shape == (sz["vocab"],) and np.isfinite(logits).all()
    top, worst = int(logits.argmax()), int(logits.argmin())
    assert chip_smoke._near_tie(logits, top, top) == 0.0
    assert 0.0 < chip_smoke._near_tie(logits, top, worst) < 20.0
    # two engines agreeing on one wrong token is no tie
    assert (chip_smoke._near_tie(logits, worst, worst)
            == chip_smoke._near_tie(logits, top, worst))


def test_main_refuses_a_cpu(capsys):
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr().out
    assert "platform=cpu" in out and '"ok"' not in out


def test_size_table_is_transformer_base():
    for phase in ("train", "serve", "mesh", "pool"):
        sz = chip_smoke.SIZES[phase]
        assert (sz["n_layer"], sz["n_head"], sz["d_model"], sz["d_inner"],
                sz["vocab"]) == (6, 8, 512, 2048, 30000), phase
    assert chip_smoke.SIZES["train"]["batch"] == 64
    assert chip_smoke.SIZES["train"]["seq"] == 256
    assert chip_smoke.SIZES["conv"]["batch"] == 128
    serve = chip_smoke.SIZES["serve"]
    assert serve["slots"] >= 16 and serve["max_seq_len"] >= 2048
    assert len(serve["prompt_lens"]) == 8
    assert max(serve["prompt_lens"]) > serve["chunk"]
    json.dumps(chip_smoke.SIZES)  # printable as it stands
