"""np.random.seed(N) must pin startup init on the VERY FIRST run in a
process: the first `import jax` consumes ambient np.random state during
import, and Executor._rng_key snapshots/restores around it so the seed
draw is position-independent.  Regression: before the fix, first-call
init differed from every later call's under the same seed."""
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_SCRIPT = r"""
import sys
import numpy as np
import paddle_tpu as fluid

fluid.unique_name.switch()
main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    x = fluid.layers.data(name="x", shape=[8], dtype="float32")
    fluid.layers.fc(x, size=8)
exe = fluid.Executor(fluid.CPUPlace())
with fluid.scope_guard(fluid.Scope()):
    np.random.seed(1234)
    exe.run(startup)   # FIRST run in this process: triggers the jax import
    w1 = np.asarray(fluid.global_scope()["fc_0.w_0"]).copy()
with fluid.scope_guard(fluid.Scope()):
    np.random.seed(1234)
    exe.run(startup)   # second run: jax already imported
    w2 = np.asarray(fluid.global_scope()["fc_0.w_0"]).copy()
assert np.array_equal(w1, w2), (
    "first-run init differs from second-run init under the same seed: "
    "max delta %g" % np.abs(w1 - w2).max())
print("OK", float(w1.ravel()[0]))
"""


def test_first_run_init_matches_later_runs():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert r.stdout.startswith("OK")
