"""Test env: an 8-device virtual CPU mesh, set up BEFORE jax is imported.

Unit tests are hermetic and never touch a chip (that is ``chip_smoke.py``'s
job): ``JAX_PLATFORMS=cpu`` with eight forced host devices, exported so
test subprocesses inherit it.  The persistent compile cache is off for the
whole run (``JAX_ENABLE_COMPILATION_CACHE=0``, inherited too): six xdist
workers and the gate subprocesses they start would otherwise share
``<repo>/.jax_cache``.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"
os.environ["XLA_FLAGS"] = " ".join(
    [f for f in os.environ.get("XLA_FLAGS", "").split()
     if "xla_force_host_platform_device_count" not in f]
    + ["--xla_force_host_platform_device_count=8"])
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest


def pytest_configure(config):
    if jax.default_backend() != "cpu" or jax.device_count() < 8:
        raise pytest.UsageError(
            "hermetic test env broken: backend=%r device_count=%s (want cpu "
            "with >=8 virtual devices)"
            % (jax.default_backend(), jax.device_count()))


@pytest.fixture(autouse=True)
def _fresh_namespace():
    """Each test gets a fresh unique_name namespace and default programs."""
    import paddle_tpu.unique_name as un
    from paddle_tpu import framework

    old_gen = un.switch()
    old_main = framework.switch_main_program(framework.Program())
    old_startup = framework.switch_startup_program(framework.Program())
    yield
    un.switch(old_gen)
    framework.switch_main_program(old_main)
    framework.switch_startup_program(old_startup)
