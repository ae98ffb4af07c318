"""Compute-side introspection plane (observability.xla_stats): XLA
cost/memory capture on real executor runs, the static ``compute.*``
gauges and their /metrics export (engine- and pool-level), bitwise
neutrality with the plane armed, and the disabled-path budget.  (Step
time, MFU and BW-util left the plane in PR 24: utilization is measured
from a device trace, docs/observability.md "Phases".)
"""
import os
import tempfile
import urllib.request

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import observability as obs  # noqa: E402
from paddle_tpu import serving  # noqa: E402
from paddle_tpu.observability import xla_stats  # noqa: E402


@pytest.fixture(autouse=True)
def _plane_off():
    """Every test starts and ends with the plane disarmed and empty, and
    with any peak overrides cleared."""
    xla_stats.disable()
    xla_stats.reset()
    xla_stats.configure_peaks(None, None)
    yield
    xla_stats.disable()
    xla_stats.reset()
    xla_stats.configure_peaks(None, None)


def _mlp_train_program(seed=3):
    main = fluid.Program()
    startup = fluid.Program()
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=16, act="relu")
        p = fluid.layers.fc(input=h, size=4, act="softmax")
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(input=p, label=y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    rng = np.random.RandomState(seed)
    feed = {"x": rng.randn(16, 8).astype(np.float32),
            "y": rng.randint(0, 4, (16, 1)).astype(np.int64)}
    return main, startup, loss, feed


def _run_steps(main, startup, loss, feed, steps=4):
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(steps):
            exe.run(main, feed=feed, fetch_list=[loss])
        params = {
            n: np.asarray(scope.vars[n])
            for n in main.persistable_names()
            if n in scope.vars and n != "__rng_key__"
        }
    return params


def test_capture_populates_gauges_for_bound_training_step():
    """Flops / bytes / peak-HBM / intensity all live after a bound
    (fast-path) training step, and nothing dynamic is published."""
    xla_stats.enable(peak_flops=1e12, peak_membw=1e11)
    main, startup, loss, feed = _mlp_train_program()
    _run_steps(main, startup, loss, feed, steps=4)  # step 2+ replays bound

    for name in ("compute.flops_per_step", "compute.bytes_per_step",
                 "compute.peak_hbm_bytes", "compute.arith_intensity"):
        v = obs.gauge(name).value
        assert isinstance(v, float) and v > 0, (name, v)

    st = xla_stats.program_stats(
        "%x:v%d" % (id(main), getattr(main, "version", 0)))
    assert st is not None
    assert st.flops > 0 and st.bytes_accessed > 0
    assert st.peak_hbm_bytes == st.arg_bytes + st.out_bytes + st.temp_bytes
    assert set(xla_stats.GAUGES) >= {
        n for n in obs.get_telemetry().gauges() if n.startswith("compute.")}
    for gone in ("compute.mfu", "compute.bw_util", "compute.step_time_s"):
        assert gone not in xla_stats.GAUGES
        assert obs.gauge(gone).value is None


def test_gauges_visible_in_metrics_scrape_and_summary():
    xla_stats.enable(peak_flops=1e12, peak_membw=1e11)
    main, startup, loss, feed = _mlp_train_program()
    _run_steps(main, startup, loss, feed, steps=3)
    text = obs.render_prometheus()
    samples = obs.parse_prometheus(text)  # strict: rejects dup families
    for name in ("compute.flops_per_step", "compute.peak_hbm_bytes",
                 "compute.bytes_per_step"):
        prom = obs.prometheus_name(name)
        assert prom in samples and samples[prom] > 0, prom
    rep = xla_stats.summary()
    assert "GFLOPs" in rep and "intensity" in rep


def test_bitwise_neutrality_plane_on_vs_off():
    """Arming the plane must not change one bit of training: capture is
    an AOT lower+compile on the side, never a semantic change."""
    main, startup, loss, feed = _mlp_train_program(seed=11)
    base = _run_steps(main, startup, loss, feed, steps=5)

    fluid.unique_name.switch()
    main2, startup2, loss2, feed2 = _mlp_train_program(seed=11)
    xla_stats.enable(peak_flops=1e12, peak_membw=1e11)
    armed = _run_steps(main2, startup2, loss2, feed2, steps=5)

    assert set(base) == set(armed)
    for n in base:
        assert np.array_equal(base[n], armed[n]), n
    # and the plane really was live during the armed run
    assert xla_stats.program_stats() is not None


def test_disabled_path_cost_within_budget():
    """Plane off, an entry's runner pays one flag read per step and
    ``Executor.run`` itself none; budget matches the PR-4 gate (2us
    nominal, 10us CI slack)."""
    import time

    assert not xla_stats.active()
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        xla_stats.active()
    per_active = (time.perf_counter() - t0) / n
    budget = 10e-6
    assert per_active < budget, "active() costs %.2fus" % (per_active * 1e6)


def test_peak_table_and_overrides(monkeypatch):
    f, b = xla_stats.device_peaks("TPU v4")
    assert f == 275e12 and b == 1228e9
    with pytest.raises(ValueError, match="matches no row of PEAK_TABLE"):
        xla_stats.device_peaks("weird accelerator")
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "123.0")
    monkeypatch.setenv("PADDLE_TPU_PEAK_BW", "7.0")
    assert xla_stats.device_peaks("TPU v4") == (123.0, 7.0)


def test_restore_defaults_clears_override_leak():
    xla_stats.enable(peak_flops=123.0, peak_membw=7.0)
    xla_stats.disable()
    assert xla_stats._peaks("TPU v4") == (123.0, 7.0)  # leaks by design
    xla_stats.restore_defaults()
    assert xla_stats._peaks("TPU v4") == (275e12, 1228e9)


def test_capture_failure_counts_not_raises():
    class Boom:
        def lower(self, *a):
            raise RuntimeError("no backend")

    errs0 = obs.counter("compute.capture_errors").value
    assert xla_stats.capture_jitted("t", Boom(), (1,)) is None
    assert obs.counter("compute.capture_errors").value == errs0 + 1


def _save_model(dirname, seed=5, width=8):
    fluid.unique_name.switch()
    main = fluid.Program()
    startup = fluid.Program()
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[width], dtype="float32")
        out = fluid.layers.fc(x, size=4, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(dirname, ["x"], [out], exe,
                                      main_program=main)
    return dirname


def test_pool_serve_metrics_exports_compute_families():
    """Satellite: the compute.* families ride a ReplicaPool's /metrics
    endpoint, and the whole exposition stays duplicate-family clean with
    the new families added (parse_prometheus rejects regressions)."""
    xla_stats.enable(peak_flops=1e12, peak_membw=1e11)
    rng = np.random.RandomState(0)
    with tempfile.TemporaryDirectory() as td:
        mdir = _save_model(os.path.join(td, "m"))
        pool = serving.ReplicaPool(mdir, replicas=2, batch_buckets=(2, 4),
                                   batch_timeout_ms=0.5, warmup=False,
                                   supervise=False)
        try:
            for _ in range(6):
                pool.predict({"x": rng.randn(1, 8).astype(np.float32)},
                             timeout=60)
            srv = pool.serve_metrics()
            with urllib.request.urlopen(srv.url + "/metrics",
                                        timeout=5) as resp:
                body = resp.read().decode()
        finally:
            pool.stop()
    samples = obs.parse_prometheus(body)  # raises on duplicate families
    for name in ("compute.flops_per_step", "compute.peak_hbm_bytes",
                 "compute.bytes_per_step"):
        prom = obs.prometheus_name(name)
        assert prom in samples and samples[prom] > 0, prom
    # pool-level serving families still alongside, one scrape for both
    assert obs.prometheus_name("serving.replica.pool_size") in samples
