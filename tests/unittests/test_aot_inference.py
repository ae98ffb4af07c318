"""AOT inference artifact (io.save_inference_model(aot=True)): a compiled
executable serialized via jax.export, loadable in a FRESH process with no
Program rebuild and no re-trace, matching in-process outputs exactly.
Reference analog: the C++ predictor deployment path
(paddle/fluid/inference/api/paddle_inference_api.h)."""
import os
import subprocess
import sys

import numpy as np

import paddle_tpu as fluid

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build_and_save(dirname):
    fluid.unique_name.switch()
    main = fluid.Program()
    startup = fluid.Program()
    startup.random_seed = 17
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h = fluid.layers.fc(x, size=16, act="relu")
        out = fluid.layers.fc(h, size=4, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    fluid.io.save_inference_model(dirname, ["x"], [out], exe,
                                  main_program=main, aot=True)
    X = np.random.RandomState(0).randn(6, 8).astype("float32")
    want = exe.run(main, feed={"x": X}, fetch_list=[out])[0]
    return X, np.asarray(want)


def test_aot_roundtrip_in_process(tmp_path):
    d = str(tmp_path / "model")
    with fluid.scope_guard(fluid.Scope()):
        X, want = _build_and_save(d)
    assert os.path.exists(os.path.join(d, "__aot__"))
    predict, feed_names, fetch_names = fluid.io.load_aot_inference_model(d)
    assert feed_names == ["x"]
    got = predict({"x": X})[0]
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    # the batch dim exported symbolically: other batch sizes, same artifact
    X2 = np.random.RandomState(1).randn(3, 8).astype("float32")
    assert predict({"x": X2})[0].shape == (3, 4)


def test_aot_fresh_process_standalone_predictor(tmp_path):
    """save in THIS process; predict via tools/predict.py in a fresh
    interpreter that never imports paddle_tpu — identical outputs."""
    d = str(tmp_path / "model")
    with fluid.scope_guard(fluid.Scope()):
        X, want = _build_and_save(d)
    xfile = str(tmp_path / "x.npy")
    ofile = str(tmp_path / "out.npz")
    np.save(xfile, X)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = ""  # prove: no paddle_tpu on the path
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "predict.py"),
         d, xfile, "--out", ofile],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    got = np.load(ofile)
    (fetch_name,) = list(got.keys())
    np.testing.assert_allclose(got[fetch_name], want, rtol=1e-6, atol=1e-7)


def test_aot_conv_model_roundtrip(tmp_path):
    """Conv/pool/bn models export under the symbolic batch dim too (the
    actual deployment shape for the image models)."""
    fluid.unique_name.switch()
    main = fluid.Program()
    startup = fluid.Program()
    startup.random_seed = 21
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[3, 16, 16], dtype="float32")
        c = fluid.layers.conv2d(img, num_filters=8, filter_size=3, act="relu")
        c = fluid.layers.batch_norm(c, is_test=True)
        p = fluid.layers.pool2d(c, pool_size=2, pool_stride=2)
        out = fluid.layers.fc(p, size=10, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    d = str(tmp_path / "convmodel")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(d, ["img"], [out], exe,
                                      main_program=main, aot=True)
        X = np.random.RandomState(3).randn(4, 3, 16, 16).astype("float32")
        want = np.asarray(exe.run(main, feed={"img": X}, fetch_list=[out])[0])
    predict, _, _ = fluid.io.load_aot_inference_model(d)
    np.testing.assert_allclose(predict({"img": X})[0], want,
                               rtol=1e-5, atol=1e-6)
    # different batch size, same artifact
    X2 = np.random.RandomState(4).randn(2, 3, 16, 16).astype("float32")
    assert predict({"img": X2})[0].shape == (2, 10)


def test_aot_int8_model_roundtrip(tmp_path):
    """The int8-quantized inference program (Int8InferenceTranspiler)
    exports and reloads as an AOT artifact: quantized deployment parity
    with the reference's int8 C++ predictor path."""
    from paddle_tpu.contrib.quantize import Int8InferenceTranspiler

    fluid.unique_name.switch()
    main = fluid.Program()
    startup = fluid.Program()
    startup.random_seed = 29
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[3, 8, 8], dtype="float32")
        c = fluid.layers.conv2d(img, num_filters=4, filter_size=3, act="relu")
        out = fluid.layers.fc(c, size=6, act="softmax")
    infer = main.clone(for_test=True)
    exe = fluid.Executor(fluid.CPUPlace())
    d = str(tmp_path / "int8model")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        Int8InferenceTranspiler().transpile(infer, fluid.global_scope())
        assert any(op.type.startswith("quantized_")
                   for op in infer.global_block().ops)
        X = np.random.RandomState(5).randn(4, 3, 8, 8).astype("float32")
        want = np.asarray(exe.run(infer, feed={"img": X}, fetch_list=[out])[0])
        fluid.io.save_inference_model(d, ["img"], [out], exe,
                                      main_program=infer, aot=True)
    predict, _, _ = fluid.io.load_aot_inference_model(d)
    np.testing.assert_allclose(predict({"img": X})[0], want,
                               rtol=1e-5, atol=1e-6)


def test_aot_embedding_model_int64_feeds(tmp_path):
    """int64 token feeds (embedding models) export and predict; the CLI
    casts loaded arrays to the exported dtypes."""
    fluid.unique_name.switch()
    main = fluid.Program()
    startup = fluid.Program()
    startup.random_seed = 37
    with fluid.program_guard(main, startup):
        w = fluid.layers.data(name="w", shape=[6], dtype="int64")
        emb = fluid.layers.embedding(w, size=[50, 16])
        pooled = fluid.layers.reduce_mean(emb, dim=1)
        out = fluid.layers.fc(pooled, size=5, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    d = str(tmp_path / "embmodel")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(d, ["w"], [out], exe,
                                      main_program=main, aot=True)
        W = np.random.RandomState(6).randint(0, 50, size=(3, 6)).astype("int64")
        want = np.asarray(exe.run(main, feed={"w": W}, fetch_list=[out])[0])
    predict, _, _ = fluid.io.load_aot_inference_model(d)
    np.testing.assert_allclose(predict({"w": W})[0], want, rtol=1e-6, atol=1e-7)


def test_aot_pipelined_model_static_batch(tmp_path):
    """A layers.Pipeline model AOT-exports with a STATIC batch override
    (the microbatch split needs concrete B); symbolic batch raises the
    documented error."""
    fluid.unique_name.switch()
    main = fluid.Program()
    startup = fluid.Program()
    startup.random_seed = 41
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        pipe = fluid.layers.Pipeline(num_stages=2, num_microbatches=2)
        with pipe.stage():
            h = pipe.stage_input(x)
            o = fluid.layers.fc(h, size=8, act="tanh")
            pipe.stage_output(o)
        out = fluid.layers.fc(pipe(), size=3, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    d = str(tmp_path / "pipemodel")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        try:
            fluid.io.save_inference_model(d, ["x"], [out], exe,
                                          main_program=main, aot=True)
            symbolic_ok = True
        except ValueError as e:
            symbolic_ok = False
            assert "static batch" in str(e)
        assert not symbolic_ok
        fluid.io.save_inference_model(
            d, ["x"], [out], exe, main_program=main, aot=True,
            aot_feed_shapes={"x": (4, 8)})
        X = np.random.RandomState(7).randn(4, 8).astype("float32")
        want = np.asarray(exe.run(main, feed={"x": X}, fetch_list=[out])[0])
    predict, _, _ = fluid.io.load_aot_inference_model(d)
    np.testing.assert_allclose(predict({"x": X})[0], want,
                               rtol=1e-6, atol=1e-7)


def test_aot_requires_static_nonbatch_dims(tmp_path):
    fluid.unique_name.switch()
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        # ragged time dim: shape (-1, -1, 8) has a dynamic NON-batch dim
        x = fluid.layers.data(name="x", shape=[-1, -1, 8], dtype="float32",
                              append_batch_size=False)
        out = fluid.layers.relu(x)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        try:
            fluid.io.save_inference_model(
                str(tmp_path / "m"), ["x"], [out], exe, main_program=main,
                aot=True)
            raised = False
        except ValueError as e:
            raised = "static non-batch dims" in str(e)
    assert raised
