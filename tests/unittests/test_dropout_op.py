"""dropout: is_test passthrough, train-mode keep statistics and scaling
semantics for both implementations (reference: test_dropout_op.py)."""
import numpy as np
import pytest

import paddle_tpu as fluid
from op_test import OpHarness, check_output

L = fluid.layers


def test_is_test_passthrough():
    rng = np.random.RandomState(0)
    x = rng.randn(4, 6).astype("float32")

    def build(v):
        return L.dropout(v["x"], dropout_prob=0.7, is_test=True)

    # downgrade_in_infer scales by (1 - p) at inference
    check_output(build, {"x": x}, x * 0.3, rtol=1e-5)


def test_upscale_in_train_identity_at_infer():
    rng = np.random.RandomState(1)
    x = rng.randn(4, 6).astype("float32")

    def build(v):
        return L.dropout(v["x"], dropout_prob=0.7, is_test=True,
                         dropout_implementation="upscale_in_train")

    check_output(build, {"x": x}, x, rtol=1e-5)


def test_train_mode_statistics():
    rng = np.random.RandomState(2)
    x = np.ones((64, 64), "float32")
    p = 0.4

    def build(v):
        return L.dropout(v["x"], dropout_prob=p, is_test=False,
                         dropout_implementation="upscale_in_train")

    h = OpHarness(build, {"x": x})
    (got,) = h.outputs()
    got = np.asarray(got)
    kept = got != 0
    # survivors are upscaled by 1/(1-p); keep rate concentrates near 1-p
    np.testing.assert_allclose(got[kept], 1.0 / (1 - p), rtol=1e-5)
    assert abs(kept.mean() - (1 - p)) < 0.03, kept.mean()


# --- the mask is drawn once, by the device's generator ----------------------

def _dropout_ops(program):
    return [op for op in program.global_block().ops if op.type == "dropout"]


def test_lowered_step_has_one_generator_op_a_dropout_and_no_threefry_over_a_mask():
    import re

    import jax

    from paddle_tpu.jax_bridge import init_state, program_to_fn

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = L.data(name="x", shape=[16], dtype="float32")
        h = L.dropout(L.fc(x, size=32, act="relu"), dropout_prob=0.1)
        h = L.dropout(L.fc(h, size=16), dropout_prob=0.1,
                      dropout_implementation="upscale_in_train")
        fluid.optimizer.SGD(0.1).minimize(L.reduce_mean(h))
    step = program_to_fn(main, [], return_state=True)
    text = jax.jit(step).lower(
        init_state(startup), {"x": np.ones((8, 16), "float32")},
        jax.random.PRNGKey(3)).as_text()
    assert len(_dropout_ops(main)) == 2
    # forward and backward of the two dropouts: two draws, [8, 32] and [8, 16]
    drawn = re.findall(
        r"stablehlo\.rng_bit_generator .*-> \(tensor<2xui64>, tensor<(\w+)>\)", text)
    assert sorted(drawn) == ["8x16xui32", "8x32xui32"], drawn
    # threefry is left in the arithmetic on KEYS (fold_in, split: one or two
    # words): no round of it, no xor at all, runs over a mask
    hashed = set(re.findall(r"stablehlo\.xor .*: tensor<(\w*)ui32>", text))
    assert hashed <= {"", "1x", "2x"}, hashed
    for sig in re.findall(r"func\.func private @threefry2x32\w*\((.*?)\) ->", text):
        assert set(re.findall(r"tensor<(\w*)ui32>", sig)) <= {"", "1x", "2x"}, sig


@pytest.mark.parametrize("impl", ["downgrade_in_infer", "upscale_in_train"])
def test_forward_and_backward_read_one_mask(impl):
    p, seed = 0.3, 4
    x = np.random.RandomState(3).randn(32, 48).astype("float32")

    def build(v):
        return L.dropout(v["x"], dropout_prob=p, dropout_implementation=impl)

    h = OpHarness(build, {"x": x}, grad_wrt=["x"], seed=seed)
    mask_name = _dropout_ops(h.main)[0].outputs["Mask"][0]
    # ONE run: the forward's select and the backward's read the same bits
    out, mask, dx = (np.asarray(a) for a in h.fetch(
        [h.outs[0].name, mask_name, "x@GRAD"]))
    assert mask.dtype == np.float32 and set(np.unique(mask)) == {0.0, 1.0}
    scale = np.float32(1.0 / (1.0 - p)) if impl == "upscale_in_train" else np.float32(1.0)
    np.testing.assert_allclose(out, np.where(mask > 0, x * scale, 0.0), rtol=1e-6)
    # the harness' loss is sum(out * proj), proj the first draw of its rng
    proj = np.random.RandomState(seed).uniform(0.5, 1.5, size=x.shape).astype("float32")
    np.testing.assert_allclose(dx, proj * mask * scale, rtol=1e-6)
    assert 0.55 < mask.mean() < 0.85, mask.mean()


def test_keep_rate_of_a_million_draws():
    p, n = 0.1, 2 ** 20

    def build(v):
        return L.dropout(v["x"], dropout_prob=p)

    h = OpHarness(build, {"x": np.ones((1024, 1024), "float32")})
    (mask,) = h.fetch([_dropout_ops(h.main)[0].outputs["Mask"][0]])
    kept = float(np.asarray(mask, np.float64).mean())
    sigma = (p * (1 - p) / n) ** 0.5
    assert abs(kept - (1 - p)) < 4 * sigma, (kept, sigma)


def _masks(seed, steps=2):
    """``steps`` masks of one dropout in a fresh executor and scope."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = L.data(name="x", shape=[64], dtype="float32")
        L.dropout(x, dropout_prob=0.5, seed=seed)
    mask = _dropout_ops(main)[0].outputs["Mask"][0]
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        return [np.asarray(exe.run(main, feed={"x": np.ones((64, 64), "float32")},
                                   fetch_list=[mask])[0]) for _ in range(steps)]


def test_a_seed_pins_the_mask_and_no_seed_draws_anew_every_step():
    a, b = _masks(seed=7), _masks(seed=7)
    np.testing.assert_array_equal(a[0], b[0])      # two executors, one mask
    np.testing.assert_array_equal(a[0], a[1])      # pinned across steps too
    assert (a[0] != _masks(seed=8)[0]).mean() > 0.3
    free = _masks(seed=None)
    assert (free[0] != free[1]).mean() > 0.3       # a new mask the next step
    np.testing.assert_array_equal(free[0], _masks(seed=None)[0])  # program seed


def test_counters_count_each_training_dropout_once():
    from paddle_tpu import observability as obs
    from paddle_tpu.models import transformer as T

    B, S, H, D, DI = 4, 8, 2, 16, 64
    masks = obs.counter("dropout.masks", labels={"impl": "rbg"})
    elements = obs.counter("dropout.mask_elements")
    m0, e0 = masks.value, elements.value
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = L.data(name="x", shape=[S, D], dtype="float32")
        y = T.encoder_layer(x, None, H, D // H, D // H, D, DI, 0.1)
        fluid.optimizer.SGD(0.1).minimize(L.reduce_mean(y))
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for batch in (B, B, 2 * B):   # a replay, then a retrace at a new shape
            exe.run(main, feed={"x": np.ones((batch, S, D), "float32")},
                    fetch_list=[y])
    # attention weights, the two post_process dropouts, the FFN's hidden layer
    assert masks.value - m0 == 4
    assert elements.value - e0 == B * (H * S * S + S * D + S * DI + S * D)
    # inference draws nothing
    test_prog = main.clone(for_test=True)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(test_prog, feed={"x": np.ones((B, S, D), "float32")}, fetch_list=[y])
    assert masks.value - m0 == 4
