"""Executor fast-path dispatch: bound-program cache semantics.

The fast path must be *semantically invisible*: identical results to the
slow path, invalidated by exactly the events that can change a step's
meaning (program edit, scope mutation), and never handing out a fetch
whose device buffer a later step's donation could invalidate."""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.executor import LazyFetch, _BoundProgram


def _build_train(n_layers=3, width=8, seed=77):
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[width], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            h = x
            for _ in range(n_layers):
                h = fluid.layers.fc(h, size=width, act="relu")
            pred = fluid.layers.fc(h, size=1)
            loss = fluid.layers.mean(fluid.layers.square(pred - y))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    main.random_seed = seed
    return main, startup, loss


def _feed(width=8, batch=4, seed=3):
    rng = np.random.RandomState(seed)
    return {"x": rng.randn(batch, width).astype(np.float32),
            "y": rng.randn(batch, 1).astype(np.float32)}


def _run_steps(main, startup, loss, feed, steps, fast_path, np_seed=11):
    """Fresh scope+executor, run `steps` steps; returns (losses, params)."""
    scope = fluid.Scope()
    exe = fluid.Executor()
    exe.fast_path = fast_path
    losses = []
    with fluid.scope_guard(scope):
        np.random.seed(np_seed)
        exe.run(startup)
        for _ in range(steps):
            out = exe.run(main, feed=feed, fetch_list=[loss])
            losses.append(np.asarray(out[0]).copy())
        params = {n: np.asarray(scope[n]).copy()
                  for n in sorted(main.persistable_names()) if n in scope}
    return losses, params, exe


def test_fast_path_bitwise_equal_training():
    """Acceptance: same training loop with and without the fast path gives
    bitwise-equal parameters after N steps."""
    main, startup, loss = _build_train()
    feed = _feed()
    losses_fast, params_fast, exe = _run_steps(main, startup, loss, feed, 8, True)
    losses_slow, params_slow, _ = _run_steps(main, startup, loss, feed, 8, False)
    assert exe._bound, "fast path never bound the program"
    assert set(params_fast) == set(params_slow)
    for n in params_fast:
        assert params_fast[n].tobytes() == params_slow[n].tobytes(), (
            "param %r diverged under the fast path" % n)
    for lf, ls in zip(losses_fast, losses_slow):
        assert lf.tobytes() == ls.tobytes()


def test_cache_hit_matches_cold_run():
    """A warm (bound) run returns exactly what a cold executor computes."""
    main, startup, loss = _build_train(seed=13)
    test_prog = main.clone(for_test=True)
    feed = _feed(seed=5)
    scope = fluid.Scope()
    exe = fluid.Executor()
    with fluid.scope_guard(scope):
        np.random.seed(23)
        exe.run(startup)
        warm = [np.asarray(exe.run(test_prog, feed=feed, fetch_list=[loss])[0])
                for _ in range(4)]
        # cold: fresh executor, no caches, same scope state
        cold_exe = fluid.Executor()
        cold_exe.fast_path = False
        cold = np.asarray(cold_exe.run(test_prog, feed=feed, fetch_list=[loss],
                                       use_program_cache=False)[0])
    for w in warm:
        assert w.tobytes() == cold.tobytes()


def test_scope_mutation_invalidates_bound_entry():
    main, startup, loss = _build_train(seed=21)
    test_prog = main.clone(for_test=True)
    feed = _feed(seed=9)
    scope = fluid.Scope()
    exe = fluid.Executor()
    with fluid.scope_guard(scope):
        np.random.seed(31)
        exe.run(startup)
        for _ in range(3):
            before = exe.run(test_prog, feed=feed, fetch_list=[loss])[0]
        (key, bound), = [(k, v) for k, v in exe._bound.items()
                         if isinstance(v, _BoundProgram)]
        # mutate a parameter through the public scope surface: the bound
        # entry must be invalidated and the next run must see the new value
        pname = sorted(n for n in test_prog.persistable_names()
                       if n in scope and ".w_" in n)[0]
        scope[pname] = np.zeros_like(np.asarray(scope[pname]))
        after = exe.run(test_prog, feed=feed, fetch_list=[loss])[0]
        assert np.asarray(after).tobytes() != np.asarray(before).tobytes()
        rebound = exe._bound[key]
        assert rebound is not bound, "scope mutation did not rebind"
        # ...and the shim surface (find_var().get_tensor().set) invalidates too
        bound2 = exe._bound[key]
        t = scope.find_var(pname).get_tensor()
        t.set(np.ones(t.shape(), np.float32))
        out2 = exe.run(test_prog, feed=feed, fetch_list=[loss])[0]
        assert np.asarray(out2).tobytes() != np.asarray(after).tobytes()
        assert exe._bound[key] is not bound2


def test_child_scope_shadowing_invalidates_owner_resolution():
    """A child-scope var shadowing a parent param must redirect the bound
    owner resolution (reference Scope::FindVar ancestor semantics)."""
    main, startup, loss = _build_train(seed=29)
    test_prog = main.clone(for_test=True)
    feed = _feed(seed=2)
    parent = fluid.Scope()
    exe = fluid.Executor()
    with fluid.scope_guard(parent):
        np.random.seed(41)
        exe.run(startup)
    child = parent.new_scope()
    for _ in range(3):
        base = exe.run(test_prog, feed=feed, fetch_list=[loss], scope=child)[0]
    pname = sorted(n for n in test_prog.persistable_names()
                   if n in parent and ".w_" in n)[0]
    child[pname] = np.zeros_like(np.asarray(parent[pname]))
    shadowed = exe.run(test_prog, feed=feed, fetch_list=[loss], scope=child)[0]
    assert np.asarray(shadowed).tobytes() != np.asarray(base).tobytes()
    # the parent's copy is untouched — the shadow lives in the child
    assert np.asarray(parent[pname]).any()


def test_program_version_bump_invalidates_bound_entry():
    main, startup, _ = _build_train(seed=37)
    # a hand-built program whose op attr we can edit in place
    prog = fluid.Program()
    sp = fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(prog, sp):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            y = fluid.layers.scale(x, scale=3.0)
    scope = fluid.Scope()
    exe = fluid.Executor()
    feed = {"x": np.ones((2, 4), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(sp)
        for _ in range(3):
            out = exe.run(prog, feed=feed, fetch_list=[y])
        np.testing.assert_allclose(np.asarray(out[0]), 3.0 * feed["x"])
        bound = [v for v in exe._bound.values()
                 if isinstance(v, _BoundProgram) and v.program is prog]
        assert bound and bound[0].version == prog.version
        # edit the program: attr change + the documented version bump
        scale_op = [op for op in prog.global_block().ops if op.type == "scale"][0]
        scale_op.attrs["scale"] = 5.0
        prog._bump()
        out = exe.run(prog, feed=feed, fetch_list=[y])
        np.testing.assert_allclose(np.asarray(out[0]), 5.0 * feed["x"])
        rebound = [v for v in exe._bound.values()
                   if isinstance(v, _BoundProgram) and v.program is prog]
        assert rebound[0].version == prog.version


def test_donation_never_resurrects_fetched_buffers():
    """Fetches that alias donated state (a param fetched directly, or an
    assign of one) must come back eagerly materialized, and must survive
    later steps donating/overwriting the underlying buffer."""
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            pred = fluid.layers.fc(x, size=1, param_attr=fluid.ParamAttr(name="w_fp"))
            loss = fluid.layers.mean(fluid.layers.square(pred - y))
            w_snapshot = fluid.layers.assign(
                fluid.default_main_program().global_block().var("w_fp"))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    main.random_seed = 3
    feed = _feed(width=4, batch=4, seed=8)
    scope = fluid.Scope()
    exe = fluid.Executor()
    with fluid.scope_guard(scope):
        np.random.seed(19)
        exe.run(startup)
        fetch = ["w_fp", w_snapshot, loss]
        outs = []
        for _ in range(6):
            outs.append(exe.run(main, feed=feed, fetch_list=fetch))
        assert exe._bound, "fast path never engaged"
        # steady state: param + its assign-alias are EAGER numpy; the loss
        # (fresh value, no state alias) is lazy
        w_direct, w_alias, loss_val = outs[-1]
        assert isinstance(w_direct, np.ndarray)
        assert isinstance(w_alias, np.ndarray)
        assert isinstance(loss_val, LazyFetch)
        # a lazy fetch held across further (donating) steps materializes
        # its own, still-live value
        held = outs[3][2]
        later = exe.run(main, feed=feed, fetch_list=fetch)
        held_np = np.asarray(held)
        assert np.isfinite(held_np).all()
        # SGD with a fixed feed strictly changes w each step: the held
        # snapshots must all differ (no buffer was recycled into another)
        snaps = [o[0].tobytes() for o in outs]
        assert len(set(snaps)) == len(snaps)
        # the assign alias snapshots w BEFORE the update: step i's snapshot
        # equals step i-1's post-update fetch — stale/donated buffers would
        # break this chain
        for prev, cur in zip(outs, outs[1:]):
            assert np.asarray(cur[1]).tobytes() == prev[0].tobytes()
        del later


def test_lazy_fetch_materializes_correct_numpy():
    main, startup, loss = _build_train(seed=53)
    test_prog = main.clone(for_test=True)
    feed = _feed(seed=17)
    scope = fluid.Scope()
    exe = fluid.Executor()
    with fluid.scope_guard(scope):
        np.random.seed(61)
        exe.run(startup)
        exe.fast_path = False
        expected = np.asarray(
            exe.run(test_prog, feed=feed, fetch_list=[loss])[0])
        exe.fast_path = True
        for _ in range(3):
            out = exe.run(test_prog, feed=feed, fetch_list=[loss])[0]
    assert isinstance(out, LazyFetch)
    # metadata without materialization, numpy protocol, indexing, math
    assert out.shape == tuple(expected.shape)
    assert out.dtype == expected.dtype
    assert np.asarray(out).tobytes() == expected.tobytes()
    np.testing.assert_allclose(np.ravel(out)[0], np.ravel(expected)[0])
    assert float(out + 0.0) == float(expected)
    assert (out * 2 == expected * 2).all()


def test_pinned_output_fallback_only_on_structure_change():
    """Mesh path: a step that CREATES a persistable (new_state keys differ
    from state keys) falls back to unpinned outputs and succeeds; the
    created var lands in the scope."""
    prog = fluid.Program()
    sp = fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(prog, sp):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            y = fluid.layers.scale(x, scale=2.0)
            c = fluid.layers.fill_constant([2, 2], "float32", 7.0)
    c.persistable = True  # the step now creates persistable state
    # (the setter bumps program.version, invalidating persistable_names())
    scope = fluid.Scope()
    exe = fluid.Executor()
    exe.attach_mesh(True)
    feed = {"x": np.ones((8, 4), np.float32)}
    with fluid.scope_guard(scope):
        out = exe.run(prog, feed=feed, fetch_list=[y])
        np.testing.assert_allclose(np.asarray(out[0]), 2.0 * feed["x"])
        assert c.name in scope
        np.testing.assert_allclose(np.asarray(scope[c.name]),
                                   np.full((2, 2), 7.0, np.float32))
        # second run: the created var is incoming state now; still correct
        out = exe.run(prog, feed=feed, fetch_list=[y])
        np.testing.assert_allclose(np.asarray(out[0]), 2.0 * feed["x"])


def test_pinned_output_fallback_reraises_genuine_errors():
    """Mesh path: a TypeError that is NOT the documented structure-change
    case must re-raise instead of silently re-jitting unpinned."""
    prog = fluid.Program()
    sp = fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(prog, sp):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            y = fluid.layers.scale(x, scale=2.0)
    scope = fluid.Scope()
    exe = fluid.Executor()
    exe.attach_mesh(True)
    feed = {"x": np.ones((8, 4), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(prog, feed=feed, fetch_list=[y])
        entry = next(iter(exe._cache.values()))
        with pytest.raises(TypeError):
            entry({}, {"x": feed["x"]}, "not-a-prng-key")
        # the pinned executable is still intact: a valid run succeeds
        out = exe.run(prog, feed=feed, fetch_list=[y])
        np.testing.assert_allclose(np.asarray(out[0]), 2.0 * feed["x"])


def test_bound_entry_does_not_pin_dead_scopes():
    """Bound entries hold scope references WEAKLY: a dropped scope (and
    with it a whole model's device arrays) must be collectable even while
    its bound entry is still cached on a long-lived executor."""
    import gc
    import weakref as wr

    main, startup, loss = _build_train(seed=91)
    exe = fluid.Executor()
    feed = _feed(seed=6)
    probes = []
    for _ in range(3):  # hparam-search pattern: fresh scope per trial
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            np.random.seed(5)
            exe.run(startup)
            for _ in range(3):
                exe.run(main, feed=feed, fetch_list=[loss])
        probes.append(wr.ref(scope))
        del scope
    gc.collect()
    assert all(p() is None for p in probes), (
        "executor bound cache kept dropped scopes (and their device "
        "arrays) alive")


def test_lod_feed_after_bind_takes_slow_path():
    """A LoDArray feed whose .shape/.dtype match the bound plan must MISS
    the fast path (it needs _prepare_feed's companion handling), not be
    blindly asarray'd into the jit."""
    from paddle_tpu.lod import LoDArray

    main, startup, loss = _build_train(seed=83)
    test_prog = main.clone(for_test=True)
    feed = _feed(batch=4, seed=4)
    scope = fluid.Scope()
    exe = fluid.Executor()
    with fluid.scope_guard(scope):
        np.random.seed(89)
        exe.run(startup)
        for _ in range(3):
            ref = exe.run(test_prog, feed=feed, fetch_list=[loss])
        lod_feed = {"x": LoDArray(feed["x"], np.array([1, 1, 1, 1], np.int32)),
                    "y": feed["y"]}
        out = exe.run(test_prog, feed=lod_feed, fetch_list=[loss])
        assert np.isfinite(float(np.asarray(out[0])))
        # and the bound plain-array path still works afterwards
        again = exe.run(test_prog, feed=feed, fetch_list=[loss])
        assert np.asarray(again[0]).tobytes() == np.asarray(ref[0]).tobytes()


def test_persistable_flag_flip_invalidates_state_collection():
    """`var.persistable = True` after a first run must be picked up by the
    executor's state collection (the setter bumps program.version)."""
    prog = fluid.Program()
    sp = fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(prog, sp):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            c = fluid.layers.fill_constant([2, 2], "float32", 9.0)
            y = fluid.layers.scale(x, scale=2.0)
    scope = fluid.Scope()
    exe = fluid.Executor()
    feed = {"x": np.ones((2, 4), np.float32)}
    with fluid.scope_guard(scope):
        for _ in range(2):
            exe.run(prog, feed=feed, fetch_list=[y])
        assert c.name not in scope  # plain temp: not collected
        c.persistable = True  # public flag flip, no manual _bump
        exe.run(prog, feed=feed, fetch_list=[y])
        assert c.name in scope
        np.testing.assert_allclose(np.asarray(scope[c.name]),
                                   np.full((2, 2), 9.0, np.float32))


def test_feed_shape_change_falls_back_and_rebinds():
    """A changed feed shape (last partial batch) takes the slow path for
    that step and stays correct."""
    main, startup, loss = _build_train(seed=67)
    test_prog = main.clone(for_test=True)
    scope = fluid.Scope()
    exe = fluid.Executor()
    with fluid.scope_guard(scope):
        np.random.seed(71)
        exe.run(startup)
        big = _feed(batch=8, seed=1)
        small = _feed(batch=3, seed=1)
        for _ in range(3):
            exe.run(test_prog, feed=big, fetch_list=[loss])
        out_small = exe.run(test_prog, feed=small, fetch_list=[loss])
        exe2 = fluid.Executor()
        exe2.fast_path = False
        ref_small = exe2.run(test_prog, feed=small, fetch_list=[loss],
                             use_program_cache=False)
        assert np.asarray(out_small[0]).tobytes() == np.asarray(ref_small[0]).tobytes()


def test_bfloat16_fetch_converts_as_bfloat16():
    """numpy prefers __array_interface__ to __array__, and the interface
    cannot name an extension dtype: a bf16 LazyFetch used to come back from
    np.asarray as raw '|V2' bytes (found by ResNet-50 bf16 on the chip)."""
    import jax.numpy as jnp

    from paddle_tpu.executor import LazyFetch

    got = np.asarray(LazyFetch(jnp.asarray([1.5, -2.0], jnp.bfloat16)))
    assert got.dtype == jnp.bfloat16
    assert [float(v) for v in got] == [1.5, -2.0]
