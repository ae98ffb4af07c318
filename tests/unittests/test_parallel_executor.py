"""ParallelExecutor over the virtual 8-device CPU mesh: data-parallel
training must match single-device training exactly (grad all-reduce = psum),
mirroring the reference's test_parallel_executor_* equivalence strategy."""
import numpy as np
import pytest

import jax

import paddle_tpu as fluid


def _build(seed=21):
    fluid.unique_name.switch()  # names restart at fc_0 for each build
    main = fluid.Program()
    startup = fluid.Program()
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=16, act="relu")
        p = fluid.layers.fc(input=h, size=4, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(input=p, label=y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def test_parallel_matches_single_device():
    assert jax.device_count() >= 8
    rng = np.random.RandomState(0)
    B = 32  # divisible by 8
    X = rng.randn(B, 8).astype("float32")
    Y = rng.randint(0, 4, size=(B, 1)).astype("int64")

    # single device
    main, startup, loss = _build()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        single_losses = [
            float(np.ravel(exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])[0])[0])
            for _ in range(5)
        ]
        w_single = np.asarray(fluid.global_scope()["fc_0.w_0"]).copy()

    # data-parallel over all devices
    main2, startup2, loss2 = _build()
    exe2 = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe2.run(startup2)
        pexe = fluid.ParallelExecutor(loss_name=loss2.name, main_program=main2)
        par_losses = [
            float(np.ravel(pexe.run(fetch_list=[loss2], feed={"x": X, "y": Y})[0]).mean())
            for _ in range(5)
        ]
        w_par = np.asarray(fluid.global_scope()["fc_0.w_0"]).copy()

    np.testing.assert_allclose(par_losses, single_losses, rtol=1e-5)
    np.testing.assert_allclose(w_par, w_single, rtol=1e-5, atol=1e-6)


def test_parallel_executor_dp_tp_mesh_matches_single_device():
    """First-class tp through the user API: ParallelExecutor(mesh_shape=(4,2))
    Megatron-shards parameters over the tp axis and must reproduce
    single-device numerics exactly (XLA inserts the collectives)."""
    assert jax.device_count() >= 8
    rng = np.random.RandomState(7)
    B = 32
    X = rng.randn(B, 8).astype("float32")
    Y = rng.randint(0, 4, size=(B, 1)).astype("int64")

    main, startup, loss = _build(seed=11)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        single_losses = [
            float(np.ravel(exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])[0])[0])
            for _ in range(4)
        ]
        w_single = np.asarray(fluid.global_scope()["fc_0.w_0"]).copy()

    main2, startup2, loss2 = _build(seed=11)
    exe2 = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe2.run(startup2)
        pexe = fluid.ParallelExecutor(
            loss_name=loss2.name, main_program=main2, mesh_shape=(4, 2))
        assert pexe._mesh.axis_names == ("dp", "tp")
        tp_losses = [
            float(np.ravel(pexe.run(fetch_list=[loss2], feed={"x": X, "y": Y})[0]).mean())
            for _ in range(4)
        ]
        w_tp = np.asarray(fluid.global_scope()["fc_0.w_0"]).copy()

    np.testing.assert_allclose(tp_losses, single_losses, rtol=1e-5)
    np.testing.assert_allclose(w_tp, w_single, rtol=1e-4, atol=1e-6)


def test_executor_runs_on_state_a_dp_tp_step_left_tp_split():
    """Train with ParallelExecutor, evaluate with the plain Executor on the
    same scope (the standard Fluid flow): after a dp x tp step the weights
    in the scope are split over the tp axis, and the single-device step —
    which states its placement — has to gather them, not refuse them."""
    rng = np.random.RandomState(7)
    X = rng.randn(32, 8).astype("float32")
    Y = rng.randint(0, 4, size=(32, 1)).astype("int64")
    feed = {"x": X, "y": Y}

    def losses(parallel):
        main, startup, loss = _build(seed=11)
        test_prog = main.clone(for_test=True)
        exe = fluid.Executor(fluid.CPUPlace())
        out = []
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            # bind the eval entry BEFORE the layout changes under it
            out.append(exe.run(test_prog, feed=feed, fetch_list=[loss])[0])
            if parallel:
                stepper = fluid.ParallelExecutor(
                    loss_name=loss.name, main_program=main, mesh_shape=(2, 2))
                stepper.run(fetch_list=[loss], feed=feed)
                w = fluid.global_scope()["fc_0.w_0"]
                assert not w.sharding.is_fully_replicated, w.sharding
            else:
                exe.run(main, feed=feed, fetch_list=[loss])
            out.append(exe.run(test_prog, feed=feed, fetch_list=[loss])[0])
            out.append(fluid.Executor(fluid.CPUPlace()).run(
                test_prog, feed=feed, fetch_list=[loss])[0])
            # a single-device TRAINING step on the split state, and back
            out.append(exe.run(main, feed=feed, fetch_list=[loss])[0])
            if parallel:
                out.append(stepper.run(fetch_list=[loss], feed=feed)[0])
            else:
                out.append(exe.run(main, feed=feed, fetch_list=[loss])[0])
        return [float(np.ravel(np.asarray(v)).mean()) for v in out]

    single, mixed = losses(False), losses(True)
    assert single[1] < single[0] and single[4] < single[3]
    np.testing.assert_allclose(mixed, single, rtol=1e-5)


def test_parallel_executor_dp_tp_transformer_matches_replicated():
    """VERDICT r3 item 3 'done' criterion: the transformer trained via
    ParallelExecutor on a dp4xtp2 mesh matches replicated numerics, without
    the user ever touching jax_bridge."""
    from paddle_tpu.models import transformer as T

    assert jax.device_count() >= 8
    rng = np.random.RandomState(3)
    B, S = 8, 16
    kw = dict(batch_size=B, seq_len=S, src_vocab_size=64, trg_vocab_size=64,
              max_length=S + 2, n_layer=1, n_head=2, d_model=16, d_inner=32,
              dropout=0.0)
    src = rng.randint(1, 64, size=(B, S)).astype("int64")
    trg = rng.randint(1, 64, size=(B, S)).astype("int64")
    lbl = rng.randint(1, 64, size=(B, S)).astype("int64")
    feed = {"src_word": src, "trg_word": trg, "lbl_word": lbl}

    def run_steps(parallel):
        fluid.unique_name.switch()
        model = T.get_model(**kw)
        model["startup"].random_seed = 9
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(model["startup"])
            if parallel:
                runner = fluid.ParallelExecutor(
                    loss_name=model["loss"].name, main_program=model["main"],
                    mesh_shape=(4, 2))
                losses = [
                    float(np.ravel(runner.run(fetch_list=[model["loss"]], feed=feed)[0]).mean())
                    for _ in range(3)
                ]
            else:
                losses = [
                    float(np.ravel(exe.run(model["main"], feed=feed, fetch_list=[model["loss"]])[0])[0])
                    for _ in range(3)
                ]
        return losses

    single = run_steps(parallel=False)
    sharded = run_steps(parallel=True)
    np.testing.assert_allclose(sharded, single, rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("n_head,sp_engine", [(2, "ring"), (8, "auto"), (8, "ulysses")])
def test_parallel_executor_sp_attention_matches_single_device(n_head, sp_engine):
    """flash_attention under a mesh with an 'sp' axis runs sequence-
    parallel (ring, or ulysses when heads divide); numerics must match the
    single-device path."""
    assert jax.device_count() >= 8

    def build():
        fluid.unique_name.switch()
        main = fluid.Program()
        startup = fluid.Program()
        startup.random_seed = 13
        with fluid.program_guard(main, startup):
            q = fluid.layers.data(name="q", shape=[n_head, 16, 8], dtype="float32")
            k = fluid.layers.data(name="k", shape=[n_head, 16, 8], dtype="float32")
            v = fluid.layers.data(name="v", shape=[n_head, 16, 8], dtype="float32")
            o = fluid.layers.flash_attention(q, k, v, causal=True,
                                             sp_engine=sp_engine)
            s = fluid.layers.reduce_sum(o)
        return main, startup, s

    rng = np.random.RandomState(5)
    Q = rng.randn(4, n_head, 16, 8).astype("float32")
    K = rng.randn(4, n_head, 16, 8).astype("float32")
    V = rng.randn(4, n_head, 16, 8).astype("float32")
    feed = {"q": Q, "k": K, "v": V}

    main, startup, s = build()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        ref = exe.run(main, feed=feed, fetch_list=[s])[0]

    main2, startup2, s2 = build()
    exe2 = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe2.run(startup2)
        pexe = fluid.ParallelExecutor(
            main_program=main2, mesh_shape={"dp": 1, "sp": 8})
        got = pexe.run(fetch_list=[s2], feed=feed)[0]

    np.testing.assert_allclose(np.ravel(got), np.ravel(ref), rtol=2e-4, atol=1e-4)


def test_parallel_executor_sp_transformer_matches_single_device():
    """The REAL transformer model (use_flash) under a dp1 x sp8 mesh: its
    flash_attention ops run ring attention over the sp axis and training
    numerics match the single-device run."""
    from paddle_tpu.models import transformer as T

    assert jax.device_count() >= 8
    rng = np.random.RandomState(4)
    B, S = 4, 16
    kw = dict(batch_size=B, seq_len=S, src_vocab_size=64, trg_vocab_size=64,
              max_length=S + 2, n_layer=1, n_head=2, d_model=16, d_inner=32,
              dropout=0.0, use_flash=True)
    feed = {
        # no PAD tokens: the encoder feeds kv_lens from padding, which
        # forces the dense-kernel fallback; all-valid rows keep the ring
        # path engaged for the causal decoder self-attention
        "src_word": rng.randint(4, 64, size=(B, S)).astype("int64"),
        "trg_word": rng.randint(4, 64, size=(B, S)).astype("int64"),
        "lbl_word": rng.randint(4, 64, size=(B, S)).astype("int64"),
    }

    def run_steps(parallel):
        fluid.unique_name.switch()
        model = T.get_model(**kw)
        model["startup"].random_seed = 17
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(model["startup"])
            if parallel:
                runner = fluid.ParallelExecutor(
                    loss_name=model["loss"].name, main_program=model["main"],
                    mesh_shape={"dp": 1, "sp": 8})
                return [
                    float(np.ravel(runner.run(fetch_list=[model["loss"]], feed=feed)[0]).mean())
                    for _ in range(3)
                ]
            return [
                float(np.ravel(exe.run(model["main"], feed=feed, fetch_list=[model["loss"]])[0])[0])
                for _ in range(3)
            ]

    single = run_steps(parallel=False)
    sharded = run_steps(parallel=True)
    np.testing.assert_allclose(sharded, single, rtol=2e-4, atol=1e-6)


def test_tp_sharded_step_matches_replicated():
    """Megatron tp=2 sharding of the same step produces identical losses —
    XLA inserts the collectives, numerics are preserved."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.jax_bridge import init_state, program_to_fn
    from paddle_tpu.parallel.tp import make_param_shardings, shard_feeds

    rng = np.random.RandomState(1)
    X = rng.randn(16, 8).astype("float32")
    Y = rng.randint(0, 4, size=(16, 1)).astype("int64")
    feeds = {"x": X, "y": Y}

    main, startup, loss = _build(seed=5)
    state = init_state(startup)
    step = program_to_fn(main, [loss], return_state=True)

    (ref_loss,), ref_state = jax.jit(step)(dict(state), feeds)

    devices = jax.devices()[:4]
    mesh = Mesh(np.array(devices).reshape(2, 2), ("dp", "tp"))
    shardings = make_param_shardings(state, mesh, tp_axis="tp")
    jitted = jax.jit(step, in_shardings=(shardings, shard_feeds(feeds, mesh, "dp")))
    (tp_loss,), tp_state = jitted(dict(state), feeds)

    np.testing.assert_allclose(np.asarray(tp_loss), np.asarray(ref_loss), rtol=1e-5)
    for n in ref_state:
        np.testing.assert_allclose(
            np.asarray(tp_state[n]), np.asarray(ref_state[n]), rtol=1e-4, atol=1e-5, err_msg=n
        )


def test_parallel_executor_pure_tp_mesh_without_dp_axis():
    """A mesh with no 'dp' axis must not try to batch-shard feeds on it
    (regression: NamedSharding(P('dp')) on a ('tp',) mesh raised)."""
    main, startup, loss = _build(seed=19)
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(2)
    X = rng.randn(8, 8).astype("float32")
    Y = rng.randint(0, 4, size=(8, 1)).astype("int64")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        single = [
            float(np.ravel(exe.run(main, feed={"x": X, "y": Y}, fetch_list=[loss])[0])[0])
            for _ in range(3)
        ]

    main2, startup2, loss2 = _build(seed=19)
    exe2 = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe2.run(startup2)
        pexe = fluid.ParallelExecutor(
            loss_name=loss2.name, main_program=main2, mesh_shape={"tp": 2})
        got = [
            float(np.ravel(pexe.run(fetch_list=[loss2], feed={"x": X, "y": Y})[0]).mean())
            for _ in range(3)
        ]
    np.testing.assert_allclose(got, single, rtol=1e-5)


def test_mesh_runner_out_pinning_fallback_on_step_created_persistable():
    """The executor pins state out_shardings (reshard compiles into the
    step); a program whose step CREATES a persistable var the startup
    never initialized changes new_state's pytree structure, which must
    fall back to unpinned outputs + explicit conform — transparently."""
    fluid.unique_name.switch()
    main = fluid.Program()
    startup = fluid.Program()
    startup.random_seed = 3
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h = fluid.layers.fc(input=x, size=4)
        s = fluid.layers.reduce_sum(h)
        # persistable output var with NO startup initializer: first run's
        # input state lacks it, the step's output state includes it
        blk = main.global_block()
        acc = blk.create_var(name="step_sum_acc", shape=[1],
                             dtype="float32", persistable=True)
        fluid.layers.assign(s, output=acc)

    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(0)
    X = rng.randn(16, 8).astype("float32")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        pexe = fluid.ParallelExecutor(loss_name=s.name, main_program=main)
        (v1,) = pexe.run(fetch_list=[s], feed={"x": X})
        # the created persistable landed in the scope with the step's value
        got = float(np.ravel(np.asarray(fluid.global_scope()["step_sum_acc"]))[0])
        assert abs(got - float(np.ravel(v1).sum())) < 1e-3
        # and a second run (state now INCLUDES the var -> new jit key,
        # pinned path) still works
        (v2,) = pexe.run(fetch_list=[s], feed={"x": X})
        np.testing.assert_allclose(np.ravel(v2), np.ravel(v1), rtol=1e-5)


def test_attach_mesh_invalidates_compiled_cache():
    """Runners compiled before attach_mesh bake in the old (no-mesh)
    config; attaching a mesh must not serve them from the cache."""
    fluid.unique_name.switch()
    main = fluid.Program()
    startup = fluid.Program()
    startup.random_seed = 5
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        s = fluid.layers.reduce_sum(fluid.layers.fc(input=x, size=4))

    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(1)
    X = rng.randn(16, 8).astype("float32")
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        (v1,) = exe.run(main, feed={"x": X}, fetch_list=[s])
        assert len(exe._cache) > 0
        exe.attach_mesh({"dp": 8})
        assert len(exe._cache) == 0  # stale single-device runner dropped
        (v2,) = exe.run(main, feed={"x": X}, fetch_list=[s])
        np.testing.assert_allclose(np.ravel(v2), np.ravel(v1), rtol=1e-5)
        # the recompiled runner really is the mesh one: fc weight now
        # carries a NamedSharding from the SPMD path
        w = fluid.global_scope()["fc_0.w_0"]
        assert hasattr(w.sharding, "spec")
