"""The rows entry of the flash kernels (``flash_attention_rows``: the
projections' own ``[B, T, H * D]`` rows) and the layers and ops that reach it
(CPU interpret mode)."""
import numpy as np
import pytest
import jax.numpy as jnp

from paddle_tpu.parallel import flash_attention as FA
from paddle_tpu.parallel.flash_attention import (
    flash_attention,
    flash_attention_rows,
    mha_reference,
)

from _flash_cases import (
    _assert_out_and_grads_close,
    _force_bwd,
    _out_and_grads,
    _rand_qkvw,
)


# the rows entry itself, [B, T, H * D] in and out: (H, D) = the widths of the
# rows; the lane geometry each gives is test_flash_lane_heads'
_ROWS_WIDTHS = {"HD128": (2, 64), "HD512": (8, 64), "odd-heads": (3, 64),
                "D128": (2, 128), "HD16": (2, 8)}


# (T, S, causal, lens, dtype): self-attention under a causal mask and ragged
# lengths with a sequence of no visible key; cross-attention with more query
# rows than keys (an uneven tail block on both sides); bf16 inputs
_ROWS_CASES = {
    "causal-lens": (32, 32, True, [32, 0, 19], jnp.float32),
    "cross-tail": (40, 24, False, [24, 11, 3], jnp.float32),
    "T<S-causal": (24, 56, True, None, jnp.float32),
    "bf16": (40, 40, True, [40, 21, 3], jnp.bfloat16),
}


# every case at rows of one 128-lane block of two heads, in both engines; the
# other widths under the causal mask and ragged lengths in both engines, and
# each once more in another case
_ROWS_RUNS = (
    [("HD128", case, bwd) for case in _ROWS_CASES for bwd in ("scan", "fused")]
    + [(width, "causal-lens", bwd) for width in list(_ROWS_WIDTHS)[1:]
       for bwd in ("scan", "fused")]
    + [("HD512", "bf16", "fused"), ("odd-heads", "cross-tail", "fused"),
       ("D128", "T<S-causal", "fused"), ("HD16", "cross-tail", "scan")])


@pytest.mark.parametrize("width,case,bwd_impl", _ROWS_RUNS,
                         ids=["-".join(r) for r in _ROWS_RUNS])
def test_flash_rows_entry(width, case, bwd_impl, monkeypatch):
    """``flash_attention_rows`` on the projections' ``[B, T, H * D]`` rows:
    output and the three gradients against the plain reference on the unfolded
    heads, at 16-row blocks; the ``[B, H, T, D]`` entry gives the same BITS on
    the same data (it is the same kernels behind a transpose); a sequence
    with no visible key is exact zeros in all four."""
    _force_bwd(monkeypatch, bwd_impl)
    H, D = _ROWS_WIDTHS[width]
    T, S, causal, lens, dtype = _ROWS_CASES[case]
    B = 3
    q, k, v, w = _rand_qkvw(B, H, T, S, D, seed=18)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    kw = dict(kv_lens=lens and jnp.array(lens, jnp.int32), causal=causal)

    def rows(q, k, v, **kw):
        return flash_attention_rows(q, k, v, n_head=H, block_q=16, block_k=16,
                                    interpret=True, **kw)

    got = _out_and_grads(rows, *(FA._to_rows(x) for x in (q, k, v, w)), **kw)
    assert all(x.shape == (B, n, H * D) and x.dtype == dtype
               for x, n in zip(got, (T, T, S, S)))
    same = _out_and_grads(flash_attention, q, k, v, w, block_q=16, block_k=16,
                          interpret=True, **kw)
    for a, b in zip(got, same):
        np.testing.assert_array_equal(np.asarray(FA._from_rows(a, H)), np.asarray(b))
    want = _out_and_grads(mha_reference, *(x.astype(jnp.float32) for x in (q, k, v)),
                          w, **kw)
    if dtype == jnp.bfloat16:
        # one bf16 rounding of the result (2^-8 relative) on values of order 1
        for a, b in zip(same, want):
            np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                       rtol=2e-2, atol=2e-2)
    else:
        _assert_out_and_grads_close(same, want)
    for b, n in enumerate(lens or ()):
        assert n or not any(np.asarray(x, np.float32)[b].any() for x in got)


def test_flash_rows_refuses_rows_that_hold_no_whole_heads():
    x = jnp.zeros((2, 16, 24), jnp.float32)
    with pytest.raises(ValueError, match="whole heads"):
        flash_attention_rows(x, x, x, n_head=5)


def test_transformer_flash_matches_reference_path():
    """use_flash=True transformer produces the same loss/logits as the
    bias-based attention path (dropout off)."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as T

    rng = np.random.RandomState(0)
    B, L = 2, 16
    src = rng.randint(1, 50, size=(B, L)).astype("int64")
    trg = rng.randint(1, 50, size=(B, L)).astype("int64")
    lbl = rng.randint(1, 50, size=(B, L)).astype("int64")
    src[0, 12:] = T.PAD_IDX
    trg[0, 10:] = T.PAD_IDX
    lbl[0, 10:] = T.PAD_IDX

    results = {}
    for use_flash in (False, True):
        main = fluid.Program()
        startup = fluid.Program()
        startup.random_seed = 7
        with fluid.program_guard(main, startup):
            sw = fluid.layers.data(name="s", shape=[L], dtype="int64")
            tw = fluid.layers.data(name="t", shape=[L], dtype="int64")
            lw = fluid.layers.data(name="l", shape=[L], dtype="int64")
            avg, s_cost, tok, logits = T.transformer(
                sw, tw, lw, 60, 60, 32, n_layer=2, n_head=2, d_model=32,
                d_inner=64, dropout=0.0, use_flash=use_flash,
            )
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            (lv,) = exe.run(main, feed={"s": src, "t": trg, "l": lbl}, fetch_list=[avg])
        results[use_flash] = float(np.ravel(lv)[0])
    np.testing.assert_allclose(results[True], results[False], rtol=2e-4)


def _attention_program(use_flash, causal=True, n_head=2, d_model=32, L=16):
    """``multi_head_attention`` alone (the flash path under key lengths and,
    with ``causal``, a causal mask) with the gradient of its sum in its
    input: ``(main, startup, fetches)``."""
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as T

    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 7
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[L, d_model], dtype="float32")
        lens = fluid.layers.data(name="lens", shape=[], dtype="int32")
        y = T.multi_head_attention(
            x, None, None, None, d_model // n_head, d_model // n_head, d_model,
            n_head, use_flash=use_flash, flash_causal=causal, kv_lens=lens)
        (dx,) = fluid.backward.calc_gradient(
            fluid.layers.reduce_sum(fluid.layers.square(y)), [x])
    return main, startup, [y.name, dx.name]


def test_flash_path_of_multi_head_attention_has_no_reshape_or_transpose():
    """With ``use_flash`` the kernels read the projections' rows: the graph is
    fc x 3 -> flash_attention(n_head) -> fc and its backward, with no op that
    splits or merges heads; parameter names and shapes are the matmul-softmax
    path's, so a checkpoint written by either (or by the parent) loads."""
    flash, _, _ = _attention_program(True)
    plain, _, _ = _attention_program(False)
    kinds = [op.type for op in flash.global_block().ops]
    assert kinds.count("flash_attention") == 1
    assert not [k for k in kinds if "transpose" in k or "reshape" in k], kinds
    assert any("transpose" in k for k in (op.type for op in plain.global_block().ops))
    (op,) = [op for op in flash.global_block().ops if op.type == "flash_attention"]
    assert op.attrs["n_head"] == 2
    assert all(len(flash.global_block().var(n).shape) == 3
               for n in op.input("Q") + op.input("K") + op.input("V") + op.output("Out"))

    def params(program):
        return sorted((p.name, tuple(p.shape)) for p in program.all_parameters())
    assert params(flash) == params(plain) and len(params(flash)) == 4


def test_flash_op_without_the_head_attribute_runs_and_differentiates():
    """A program saved before ``n_head`` existed holds the op on [B, H, T, D]
    inputs and no such attribute: it lowers as before (the same kernels behind
    a transpose) and gives what the rows op gives on the same data."""
    import paddle_tpu as fluid

    B, H, L, D = 3, 2, 16, 8
    rng = np.random.RandomState(3)
    feed = {n: rng.randn(B, H, L, D).astype("float32") for n in "qkv"}
    feed["lens"] = np.array([16, 9, 0], "int32")
    got = {}
    for form in ("bhtd", "rows"):
        shape = [H, L, D] if form == "bhtd" else [L, H * D]
        main, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            q, k, v = (fluid.layers.data(name=n, shape=shape, dtype="float32")
                       for n in "qkv")
            lens = fluid.layers.data(name="lens", shape=[], dtype="int32")
            out = fluid.layers.flash_attention(
                q, k, v, kv_lens=lens, causal=True,
                n_head=H if form == "rows" else None)
            grads = fluid.backward.calc_gradient(
                fluid.layers.reduce_sum(fluid.layers.square(out)), [q, k, v])
        (op,) = [op for op in main.global_block().ops if op.type == "flash_attention"]
        assert ("n_head" in op.attrs) == (form == "rows")
        fed = dict(feed) if form == "bhtd" else dict(
            feed, **{n: np.asarray(FA._to_rows(jnp.asarray(feed[n]))) for n in "qkv"})
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            vals = exe.run(main, feed=fed, fetch_list=[out] + grads)
        got[form] = [np.asarray(x) for x in vals]
        assert all(np.isfinite(x).all() and x.any() for x in got[form])
    # (not the same bits here: inside one jitted CPU program XLA fuses the
    # interpreted kernel with the transposes around it; test_flash_rows_entry
    # holds the two entries to the same bits call by call)
    for a, b in zip(got["bhtd"], got["rows"]):
        np.testing.assert_allclose(np.asarray(FA._to_rows(jnp.asarray(a))), b,
                                   rtol=1e-5, atol=1e-5)
    ref = mha_reference(*(jnp.asarray(feed[n]) for n in "qkv"), causal=True,
                        kv_lens=jnp.asarray(feed["lens"]))
    np.testing.assert_allclose(got["bhtd"][0], np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_flash_layer_refuses_rows_of_the_wrong_rank():
    import paddle_tpu as fluid

    with fluid.program_guard(fluid.Program(), fluid.Program()):
        q = fluid.layers.data(name="q", shape=[2, 16, 8], dtype="float32")
        with pytest.raises(ValueError, match="n_head"):
            fluid.layers.flash_attention(q, q, q, n_head=2)


def test_flash_and_plain_attention_blocks_agree_with_gradients():
    """The rows path against the matmul-softmax path of the same block on the
    same weights (no mask: full key lengths, not causal): output and the
    gradient that reaches the block's input."""
    import paddle_tpu as fluid

    rng = np.random.RandomState(1)
    feed = {"x": rng.randn(3, 16, 32).astype("float32"),
            "lens": np.array([16, 16, 16], "int32")}
    got = {}
    for use_flash in (False, True):
        main, startup, fetches = _attention_program(use_flash, causal=False)
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            got[use_flash] = [np.asarray(v) for v in exe.run(
                main, feed=feed, fetch_list=fetches)]
    for a, b in zip(got[True], got[False]):
        assert a.shape == (3, 16, 32) and b.any()
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
