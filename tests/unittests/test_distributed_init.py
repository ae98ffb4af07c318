"""init_distributed / shutdown_distributed (multi-host runtime wiring,
SURVEY §2.4).  The actual initialize is process-global, so the happy path
runs in a subprocess; validation paths run in-process."""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

from paddle_tpu.parallel import collective as C


def _free_port():
    """Reserve an ephemeral port: bind, read the number, release it (the
    coordinator in the subprocess rebinds it an instant later)."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_validation(monkeypatch):
    monkeypatch.delenv("PADDLE_COORDINATOR", raising=False)
    with pytest.raises(ValueError, match="out of range"):
        C.init_distributed(num_processes=2, process_id=5)
    with pytest.raises(ValueError, match="coordinator_address"):
        C.init_distributed(num_processes=2, process_id=0)
    # single host without a coordinator is a documented no-op
    C.init_distributed()


def test_single_process_lifecycle():
    port = _free_port()
    code = (
        "from paddle_tpu.parallel import collective as C\n"
        "C.init_distributed('localhost:%d', 1, 0)\n" % port
        + "C.init_distributed('localhost:%d', 1, 0)  # repeat: no-op\n" % port
        + "import jax; assert jax.process_count() == 1\n"
        "C.shutdown_distributed()\n"
        "C.shutdown_distributed()\n"
        "print('LIFECYCLE-OK')\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=180)
    assert r.returncode == 0, r.stderr
    assert "LIFECYCLE-OK" in r.stdout


def test_two_process_psum_over_localhost():
    """A real 2-process jax.distributed session: each worker brings 2 cpu
    devices, the global mesh spans 4, and a cross-process psum agrees
    (SURVEY §2.4 multi-host readiness, closed end-to-end)."""
    port = _free_port()
    worker = (
        "import sys, functools\n"
        "import numpy as np\n"
        "from paddle_tpu.parallel import collective as C\n"
        "C.init_distributed('localhost:%d', 2, int(sys.argv[1]))\n" % port
        + "import jax, jax.numpy as jnp\n"
        "from jax.sharding import Mesh, PartitionSpec as P\n"
        "import functools\n"
        "assert jax.process_count() == 2\n"
        "devs = jax.devices()\n"
        "mesh = Mesh(np.array(devs), ('dp',))\n"
        "@jax.jit\n"
        "@functools.partial(jax.shard_map, mesh=mesh, in_specs=P('dp'), out_specs=P(), check_vma=False)\n"
        "def total(x):\n"
        "    return jax.lax.psum(x.sum(), 'dp')\n"
        "n = len(devs)\n"
        "out = total(jnp.arange(n * 2, dtype=jnp.float32))\n"
        "assert float(np.asarray(out)) == float(sum(range(n * 2)))\n"
        "C.shutdown_distributed()\n"
        "print('WORKER-OK')\n"
    )
    base_flags = " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": (base_flags + " --xla_force_host_platform_device_count=2").strip()}
    procs = [subprocess.Popen([sys.executable, "-c", worker, str(i)],
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                              text=True, env=env)
             for i in range(2)]
    try:
        outs = [p.communicate(timeout=240) for p in procs]
    finally:
        for p in procs:  # a timed-out peer must not keep the port bound
            if p.poll() is None:
                p.kill()
    if any("Multiprocess computations aren't implemented" in err
           for _, err in outs):
        pytest.skip("this jax build lacks multiprocess collectives on the "
                    "CPU backend; the wiring (init/mesh/trace) ran to the "
                    "execute step")
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, err[-2000:]
        assert "WORKER-OK" in out


def test_env_defaults(monkeypatch):
    monkeypatch.delenv("PADDLE_COORDINATOR", raising=False)
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "4")
    monkeypatch.setenv("PADDLE_TRAINER_ID", "9")
    with pytest.raises(ValueError, match="out of range"):
        C.init_distributed()  # id 9 of 4: env values were read
