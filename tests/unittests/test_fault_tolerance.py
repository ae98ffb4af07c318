"""Fault-tolerant training runtime: atomic manifest-verified checkpoints,
torn-write fallback, bitwise-identical auto-resume, the on-device NaN/Inf
step guard with rewind, transient-IO retry, heartbeat failure detection,
and the compile-cache degradation path — all driven by the deterministic
fault-injection harness (paddle_tpu.testing.faults)."""
import json
import os
import time
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import resilience
from paddle_tpu.testing import faults
from paddle_tpu.trainer import (
    FailureMonitor,
    Heartbeat,
    _rotate_checkpoints,
    _serials,
    detect_failed_trainers,
    load_checkpoint,
    save_checkpoint,
)


def _train_func():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    pred = fluid.layers.fc(input=x, size=1, param_attr=fluid.ParamAttr(name="w"))
    return fluid.layers.mean(fluid.layers.square_error_cost(input=pred, label=y))


def _optimizer_func():
    return fluid.optimizer.SGD(learning_rate=0.05)


def _reader():
    rng = np.random.RandomState(0)
    w = np.array([[1.0], [2.0], [-1.0], [0.5]], "float32")
    for _ in range(8):
        x = rng.randn(16, 4).astype("float32")
        yield list(zip(x, x @ w))


def _make_trainer(cdir=None, step_interval=2, max_num=5, seed=7, **kw):
    cfg = None
    if cdir is not None:
        cfg = fluid.CheckpointConfig(
            checkpoint_dir=cdir, max_num_checkpoints=max_num,
            step_interval=step_interval)
    np.random.seed(seed)  # pins the startup init draw across runs
    return fluid.Trainer(_train_func, _optimizer_func, place=fluid.CPUPlace(),
                         checkpoint_config=cfg, **kw)


def _params(t):
    return np.asarray(t.scope.vars["w"]).copy()


def _corrupt(path, offset=None):
    data = bytearray(open(path, "rb").read())
    data[(len(data) // 2) if offset is None else offset] ^= 0xFF
    with open(path, "wb") as f:
        f.write(bytes(data))


# ---------------------------------------------------------------------------
# atomic checkpoints + manifest validation
# ---------------------------------------------------------------------------


def test_checkpoint_writes_manifest(tmp_path):
    cdir = str(tmp_path / "ckpt")
    t = _make_trainer(cdir, step_interval=4)
    t.train(num_epochs=1, reader=_reader, feed_order=["x", "y"])
    serial = _serials(cdir)[-1]
    d = os.path.join(cdir, "checkpoint_%d" % serial)
    man = json.loads(open(os.path.join(d, "MANIFEST.json")).read())
    assert set(man["files"]) == {"params.npz", "meta.json", "rng_key.npy"}
    for name, info in man["files"].items():
        assert os.path.getsize(os.path.join(d, name)) == info["size"]
    assert man["serial"] == serial
    # no staging leftovers after a clean save
    assert not [n for n in os.listdir(cdir) if n.endswith(".tmp")]


def test_torn_write_leaves_previous_latest_intact(tmp_path):
    cdir = str(tmp_path / "ckpt")
    t = _make_trainer(cdir, step_interval=4)
    t.train(num_epochs=1, reader=_reader, feed_order=["x", "y"])
    w_latest = _params(t)
    latest = _serials(cdir)[-1]

    with faults.torn_write("checkpoint_9", at_byte=64):
        with pytest.raises(IOError):
            with fluid.scope_guard(t.scope):
                save_checkpoint(t.exe, cdir, t.train_program, 9,
                                {"epoch": 0, "step": 5})
    # the kill hit the staging dir: serial 9 was never published
    assert _serials(cdir)[-1] == latest
    t2 = _make_trainer(cdir, step_interval=4)
    assert t2._serial_start == latest
    np.testing.assert_array_equal(_params(t2), w_latest)


def test_load_falls_back_to_newest_intact(tmp_path):
    cdir = str(tmp_path / "ckpt")
    t = _make_trainer(cdir, step_interval=2)
    t.train(num_epochs=1, reader=_reader, feed_order=["x", "y"])
    serials = _serials(cdir)
    assert len(serials) >= 3
    _corrupt(os.path.join(cdir, "checkpoint_%d" % serials[-1], "params.npz"))

    with fluid.scope_guard(fluid.Scope()):
        with pytest.warns(UserWarning, match="corrupt checkpoint"):
            meta = load_checkpoint(t.exe, cdir, t.train_program)
    assert meta["serial"] == serials[-2]


def test_load_skips_manifest_garbage(tmp_path):
    cdir = str(tmp_path / "ckpt")
    t = _make_trainer(cdir, step_interval=2)
    t.train(num_epochs=1, reader=_reader, feed_order=["x", "y"])
    serials = _serials(cdir)
    with open(os.path.join(cdir, "checkpoint_%d" % serials[-1],
                           "MANIFEST.json"), "w") as f:
        f.write("{not json")
    with fluid.scope_guard(fluid.Scope()):
        with pytest.warns(UserWarning, match="corrupt checkpoint"):
            meta = load_checkpoint(t.exe, cdir, t.train_program)
    assert meta["serial"] == serials[-2]


def test_load_explicit_missing_serial_lists_available(tmp_path):
    cdir = str(tmp_path / "ckpt")
    t = _make_trainer(cdir, step_interval=4)
    t.train(num_epochs=1, reader=_reader, feed_order=["x", "y"])
    import re

    available = _serials(cdir)
    with pytest.raises(IOError,
                       match=re.escape("available serials: %s" % available)):
        load_checkpoint(t.exe, cdir, t.train_program, serial=777)


def test_load_explicit_corrupt_serial_raises(tmp_path):
    cdir = str(tmp_path / "ckpt")
    t = _make_trainer(cdir, step_interval=4)
    t.train(num_epochs=1, reader=_reader, feed_order=["x", "y"])
    s = _serials(cdir)[-1]
    _corrupt(os.path.join(cdir, "checkpoint_%d" % s, "params.npz"))
    with pytest.raises(IOError, match="corrupt"):
        with fluid.scope_guard(fluid.Scope()):
            load_checkpoint(t.exe, cdir, t.train_program, serial=s)


def test_failed_load_leaves_scope_untouched(tmp_path):
    """A checkpoint that validates but is missing a persistable (e.g. saved
    by an older program revision) must not half-overwrite the scope."""
    import zlib

    cdir = str(tmp_path / "ckpt")
    t = _make_trainer(cdir, step_interval=4)
    t.train(num_epochs=1, reader=_reader, feed_order=["x", "y"])
    s = _serials(cdir)[-1]
    d = os.path.join(cdir, "checkpoint_%d" % s)
    # rewrite params.npz without "w" and keep the manifest consistent, so
    # only the completeness check can catch it
    from io import BytesIO

    data = dict(np.load(os.path.join(d, "params.npz")))
    del data["w"]
    buf = BytesIO()
    np.savez(buf, **data)
    blob = buf.getvalue()
    with open(os.path.join(d, "params.npz"), "wb") as f:
        f.write(blob)
    man = json.loads(open(os.path.join(d, "MANIFEST.json")).read())
    man["files"]["params.npz"] = {"size": len(blob),
                                  "crc32": zlib.crc32(blob) & 0xFFFFFFFF}
    with open(os.path.join(d, "MANIFEST.json"), "w") as f:
        f.write(json.dumps(man))

    scope = fluid.Scope()
    sentinel = np.full((4, 1), 7.5, "float32")
    scope["w"] = sentinel.copy()
    scope["__rng_key__"] = np.array([1, 2], "uint32")
    with fluid.scope_guard(scope):
        with pytest.raises(IOError, match="missing persistable"):
            load_checkpoint(t.exe, cdir, t.train_program, serial=s)
    np.testing.assert_array_equal(np.asarray(scope["w"]), sentinel)
    np.testing.assert_array_equal(np.asarray(scope["__rng_key__"]),
                                  np.array([1, 2], "uint32"))


def test_rotation_never_deletes_last_known_good(tmp_path):
    cdir = str(tmp_path / "ckpt")
    t = _make_trainer(cdir, step_interval=2, max_num=10)
    t.train(num_epochs=1, reader=_reader, feed_order=["x", "y"])
    serials = _serials(cdir)
    assert len(serials) >= 3
    good = serials[0]
    for s in serials[1:]:
        _corrupt(os.path.join(cdir, "checkpoint_%d" % s, "params.npz"))
    # aggressive rotation would normally keep only the newest serial, but
    # every newer one is corrupt — the oldest (intact) must survive
    _rotate_checkpoints(cdir, max_num=1)
    kept = _serials(cdir)
    assert good in kept
    assert kept[-1] == serials[-1]  # the kept window is still there too


def test_transient_io_error_during_save_retries(tmp_path):
    cdir = str(tmp_path / "ckpt")
    t = _make_trainer(cdir, step_interval=4)
    t.train(num_epochs=1, reader=_reader, feed_order=["x", "y"])
    with faults.flaky_io("params.npz", times=2) as fired:
        with fluid.scope_guard(t.scope):
            save_checkpoint(t.exe, cdir, t.train_program, 9,
                            {"epoch": 1, "step": 0})
    assert fired[0] == 2  # the fault really fired; retry absorbed it
    with fluid.scope_guard(fluid.Scope()):
        meta = load_checkpoint(t.exe, cdir, t.train_program)
    assert meta["serial"] == 9


# ---------------------------------------------------------------------------
# auto-resume
# ---------------------------------------------------------------------------


def test_resume_bitwise_identical_after_crash(tmp_path):
    """Kill training mid-epoch, corrupt the newest checkpoint (as a torn
    write would), restart with resume=True: the continued run must be
    bitwise-identical to an uninterrupted one — params, step counter and
    rng key all restored from the newest INTACT serial."""
    t_ref = _make_trainer(None)
    t_ref.train(num_epochs=1, reader=_reader, feed_order=["x", "y"])
    w_ref = _params(t_ref)

    cdir = str(tmp_path / "ckpt")
    t1 = _make_trainer(cdir, step_interval=2)

    def stop_after_5(e):
        if isinstance(e, fluid.EndStepEvent) and e.step == 4:
            t1.stop()

    t1.train(num_epochs=1, event_handler=stop_after_5, reader=_reader,
             feed_order=["x", "y"])
    serials = _serials(cdir)
    assert serials == [1, 2]
    # saved rng key == the live key at checkpoint time is what makes the
    # replayed steps draw the identical randomness stream
    _corrupt(os.path.join(cdir, "checkpoint_2", "params.npz"))

    with pytest.warns(UserWarning, match="corrupt checkpoint"):
        t2 = _make_trainer(cdir, step_interval=2)
    assert (t2._epoch_start, t2._step_start, t2._serial_start) == (0, 2, 1)
    saved_key = np.load(os.path.join(cdir, "checkpoint_1", "rng_key.npy"))
    np.testing.assert_array_equal(
        np.asarray(t2.scope.vars["__rng_key__"]), saved_key)

    executed = []
    t2.train(num_epochs=1, reader=_reader, feed_order=["x", "y"],
             event_handler=lambda e: executed.append(e.step)
             if isinstance(e, fluid.EndStepEvent) else None)
    assert executed == list(range(2, 8))
    assert _params(t2).tobytes() == w_ref.tobytes()


def test_resume_false_starts_fresh(tmp_path):
    cdir = str(tmp_path / "ckpt")
    t1 = _make_trainer(cdir, step_interval=2)
    t1.train(num_epochs=1, reader=_reader, feed_order=["x", "y"])
    t2 = _make_trainer(cdir, step_interval=2, resume=False)
    assert (t2._epoch_start, t2._step_start, t2._serial_start) == (0, 0, 0)
    assert _params(t2).tobytes() != _params(t1).tobytes()


def test_resume_pinned_serial_failure_raises(tmp_path):
    """An explicitly pinned load_serial that can't be loaded must raise —
    silently training from scratch would rotate away the checkpoints the
    user was trying to restore."""
    cdir = str(tmp_path / "ckpt")
    t1 = _make_trainer(cdir, step_interval=4)
    t1.train(num_epochs=1, reader=_reader, feed_order=["x", "y"])
    cfg = fluid.CheckpointConfig(checkpoint_dir=cdir, max_num_checkpoints=5,
                                 step_interval=4)
    cfg.load_serial = 777
    np.random.seed(7)
    with pytest.raises(IOError, match="not found"):
        fluid.Trainer(_train_func, _optimizer_func, place=fluid.CPUPlace(),
                      checkpoint_config=cfg)


def test_resume_survives_all_serials_corrupt(tmp_path):
    cdir = str(tmp_path / "ckpt")
    t1 = _make_trainer(cdir, step_interval=4)
    t1.train(num_epochs=1, reader=_reader, feed_order=["x", "y"])
    for s in _serials(cdir):
        _corrupt(os.path.join(cdir, "checkpoint_%d" % s, "params.npz"))
    with pytest.warns(UserWarning, match="auto-resume skipped"):
        t2 = _make_trainer(cdir, step_interval=4)
    assert (t2._epoch_start, t2._step_start) == (0, 0)


# ---------------------------------------------------------------------------
# NaN/Inf step guard
# ---------------------------------------------------------------------------


def test_nan_guard_skips_bad_step_bitwise(tmp_path):
    t = _make_trainer(None)
    ws, losses = [], []

    def grab(e):
        if isinstance(e, fluid.EndStepEvent):
            ws.append(_params(t))
            losses.append(float(np.ravel(np.asarray(e.metrics[0]))[0]))

    with faults.nan_feeds(at_steps=[2]):
        t.train(num_epochs=1, event_handler=grab, reader=_reader,
                feed_order=["x", "y"], nan_guard=True)
    # the poisoned step: loss went NaN on device, update skipped bitwise
    assert np.isnan(losses[2])
    assert ws[2].tobytes() == ws[1].tobytes()
    # training continued with finite steps afterwards
    assert ws[3].tobytes() != ws[2].tobytes()
    assert np.isfinite(losses[3])
    assert t.nan_bad_steps == 1 and t.nan_rewinds == 0


def test_nan_guard_rewinds_after_consecutive_failures(tmp_path):
    cdir = str(tmp_path / "ckpt")
    t = _make_trainer(cdir, step_interval=1)
    with faults.nan_feeds(at_steps=[3, 4]):
        with pytest.warns(UserWarning, match="rewound"):
            t.train(num_epochs=1, reader=_reader, feed_order=["x", "y"],
                    nan_guard=2)
    assert t.nan_bad_steps == 2
    assert t.nan_rewinds == 1
    assert np.isfinite(_params(t)).all()


def test_nan_guard_without_checkpoint_raises_on_rewind():
    t = _make_trainer(None)
    with faults.nan_feeds(at_steps=[1, 2]):
        with pytest.raises(FloatingPointError, match="no checkpoint"):
            t.train(num_epochs=1, reader=_reader, feed_order=["x", "y"],
                    nan_guard=2)


def test_nan_guard_off_has_no_verdict_and_poison_propagates():
    t = _make_trainer(None)
    ws = []

    def grab(e):
        if isinstance(e, fluid.EndStepEvent):
            ws.append(_params(t))

    with faults.nan_feeds(at_steps=[2]):
        t.train(num_epochs=1, event_handler=grab, reader=_reader,
                feed_order=["x", "y"])
    assert t.exe.last_step_ok() is None  # no guard: no verdict, no extras
    assert np.isnan(ws[2]).any()  # and the NaN really poisoned the params


def test_nan_guard_matches_unguarded_numerics_bitwise():
    """With no NaN present, the guard's select must be a bitwise no-op on
    the trained parameters (CPU-deterministic)."""

    def run(guard):
        t = _make_trainer(None)
        t.train(num_epochs=1, reader=_reader, feed_order=["x", "y"],
                nan_guard=guard)
        ok = t.exe.last_step_ok()
        return _params(t), ok

    w_off, ok_off = run(False)
    w_on, ok_on = run(True)
    assert w_on.tobytes() == w_off.tobytes()
    assert ok_off is None and ok_on is True


def test_nan_guard_noop_on_stateless_step():
    """A step that writes no state (eval/inference) has no update to skip:
    the guard emits nothing — no verdict, zero extra outputs — so guarded
    eval dispatch costs nothing."""
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            out = fluid.layers.fc(x, size=1)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(3):  # slow path, then the bound fast path
            res = exe.run(main, feed={"x": np.ones((2, 4), "float32")},
                          fetch_list=[out], nan_guard=True)
        assert len(res) == 1
        assert exe.last_step_ok() is None


def test_nan_guard_direct_executor_api():
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            loss = fluid.layers.mean(fluid.layers.fc(x, size=1))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"x": np.ones((2, 4), "float32")}
    with fluid.scope_guard(scope):
        exe.run(startup)
        out = exe.run(main, feed=feed, fetch_list=[loss], nan_guard=True)
        assert exe.last_step_ok() is True
        assert len(out) == 1  # the verdict pseudo-fetch never leaks out
        bad = {"x": np.full((2, 4), np.nan, "float32")}
        exe.run(main, feed=bad, fetch_list=[loss], nan_guard=True)
        assert exe.last_step_ok() is False
        exe.run(main, feed=feed, fetch_list=[loss])
        assert exe.last_step_ok() is None


# ---------------------------------------------------------------------------
# compile cache: where it goes, and degradation on a bad directory
# ---------------------------------------------------------------------------


@pytest.fixture()
def cache_dir_updates(monkeypatch):
    """Record (instead of apply) every ``jax_compilation_cache_dir`` update
    the code under test makes, so no test moves this process's cache."""
    import jax

    seen = []
    real = jax.config.update

    def update(name, value):
        if name == "jax_compilation_cache_dir":
            seen.append(value)
        else:
            real(name, value)

    monkeypatch.setattr(jax.config, "update", update)
    return seen


@pytest.mark.parametrize("how", ["JAX_COMPILATION_CACHE_DIR", "fixed-path"])
def test_compilation_cache_bad_dir_warns_and_continues(
        tmp_path, monkeypatch, cache_dir_updates, how):
    from paddle_tpu import executor as executor_mod

    squatter = tmp_path / "cache_squatter"
    squatter.write_text("not a directory")
    usable = tmp_path / "cache_ok"

    def place(path):
        if how == "fixed-path":
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            monkeypatch.setattr(executor_mod, "_DEFAULT_COMPILE_CACHE_DIR",
                                str(path))
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(path))

    place(squatter)
    with pytest.warns(UserWarning, match="continuing without a compile cache"):
        assert executor_mod.enable_compilation_cache() is False
    assert cache_dir_updates == []
    # and a usable dir still enables it
    place(usable)
    assert executor_mod.enable_compilation_cache() is True
    assert cache_dir_updates == (
        [str(usable)] if how == "fixed-path" else [])


@pytest.mark.parametrize("env_set", [True, False],
                         ids=["JAX_COMPILATION_CACHE_DIR-set", "unset"])
def test_compilation_cache_dir_rule(tmp_path, monkeypatch, cache_dir_updates,
                                    env_set):
    """Variable set -> jax reads it itself and no directory is set in code;
    unset -> one fixed path inside the checkout.  Executor setup goes
    through the same rule and still runs."""
    from paddle_tpu import executor as executor_mod

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(executor_mod, "_compile_cache_checked", [False])
    exe = fluid.Executor(fluid.CPUPlace())
    assert executor_mod._compile_cache_checked == [True]
    assert cache_dir_updates == (
        [] if env_set else [os.path.join(repo, ".jax_cache")])
    prog = fluid.Program()
    startup = fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(prog, startup):
            x = fluid.layers.data(name="x", shape=[2], dtype="float32")
            y = fluid.layers.scale(x, scale=2.0)
    with fluid.scope_guard(fluid.Scope()):
        (out,) = exe.run(prog, feed={"x": np.ones((1, 2), "float32")},
                         fetch_list=[y])
    np.testing.assert_allclose(np.asarray(out), 2 * np.ones((1, 2)))


# ---------------------------------------------------------------------------
# Heartbeat / detect_failed_trainers / FailureMonitor
# ---------------------------------------------------------------------------


def test_heartbeat_stale_vs_fresh(tmp_path):
    d = str(tmp_path / "hb")
    hb = Heartbeat(d, "alive", interval=0.1).start()
    with open(os.path.join(d, "dead.hb"), "w") as f:
        f.write(str(time.time() - 100))
    time.sleep(0.3)
    # "dead" must ALWAYS be detected; "alive" may flicker stale on a
    # loaded shared box (the beat thread starved past the 5s timeout) —
    # retry until it beats again rather than flaking on scheduler noise
    deadline = time.time() + 10
    while True:
        failed = detect_failed_trainers(d, timeout=5.0)
        assert "dead" in failed, failed
        if failed == ["dead"] or time.time() >= deadline:
            break
        time.sleep(0.2)
    assert failed == ["dead"]
    hb.stop()


def test_heartbeat_clean_stop_is_idempotent(tmp_path):
    d = str(tmp_path / "hb")
    hb = Heartbeat(d, "t0", interval=0.05).start()
    time.sleep(0.2)
    hb.stop()
    content = open(hb.path).read()
    time.sleep(0.2)
    assert open(hb.path).read() == content  # no beats after stop
    hb.stop()  # second stop is a no-op
    # stop() without start() must not blow up either
    Heartbeat(d, "never_started", interval=0.05).stop()


def test_detect_failed_trainers_edge_cases(tmp_path):
    d = str(tmp_path / "hb")
    assert detect_failed_trainers(d, timeout=1.0) == []  # missing dir
    os.makedirs(d)
    with open(os.path.join(d, "garbage.hb"), "w") as f:
        f.write("not a float")
    with open(os.path.join(d, "ignored.txt"), "w") as f:
        f.write(str(time.time() - 100))
    with open(os.path.join(d, "fresh.hb"), "w") as f:
        f.write(str(time.time()))
    # unparseable heartbeat counts as dead-forever; non-.hb files ignored;
    # a fresh beat within the timeout window is healthy
    assert detect_failed_trainers(d, timeout=60.0) == ["garbage"]
    # a beat older than a tiny timeout is stale
    with open(os.path.join(d, "slow.hb"), "w") as f:
        f.write(str(time.time() - 0.5))
    assert set(detect_failed_trainers(d, timeout=0.1)) == {"garbage", "slow"}


def test_failure_monitor_poll_interval_and_self_exclusion(tmp_path):
    d = str(tmp_path / "hb")
    os.makedirs(d)
    # this trainer's own beat is ancient — poll must never report self
    with open(os.path.join(d, "me.hb"), "w") as f:
        f.write(str(time.time() - 100))
    mon = FailureMonitor(d, trainer_id="me", interval=0.1, timeout=1.0,
                         check_every=100.0)
    t0 = time.time()
    assert mon.poll(now=t0) == []
    with open(os.path.join(d, "peer.hb"), "w") as f:
        f.write(str(time.time() - 100))
    assert mon.poll(now=t0 + 1) == []  # cached: within check_every
    assert mon.poll(now=t0 + 200) == ["peer"]  # rescans after the window
    mon.stop()  # never started: no-op


def test_failure_monitor_checkpoint_then_stop(tmp_path):
    """A stale peer heartbeat makes the train loop save a final checkpoint
    and stop cleanly instead of hanging."""
    hb_dir = str(tmp_path / "hb")
    cdir = str(tmp_path / "ckpt")
    os.makedirs(hb_dir)
    with open(os.path.join(hb_dir, "trainer1.hb"), "w") as f:
        f.write(str(time.time() - 100))
    t = _make_trainer(cdir, step_interval=100)  # no periodic checkpoints
    mon = FailureMonitor(hb_dir, trainer_id="trainer0", interval=0.05,
                         timeout=1.0, check_every=0.0)
    steps = []
    t.train(num_epochs=4, reader=_reader, feed_order=["x", "y"],
            event_handler=lambda e: steps.append(e.step)
            if isinstance(e, fluid.EndStepEvent) else None,
            failure_monitor=mon)
    assert mon.failed_peers == ["trainer1"]
    assert steps == []  # detected before the first step ran
    assert _serials(cdir) == [1]  # the checkpoint-then-stop artifact
    assert not mon._started  # train() stopped the monitor
    meta = json.loads(open(os.path.join(
        cdir, "checkpoint_1", "meta.json")).read())
    assert meta == {"epoch": 0, "step": 0}  # resume replays the unrun step
