"""The benchmark suite runner (benchmarks/fluid_benchmark.py — the analog
of the reference's benchmark/fluid/fluid_benchmark.py): a representative
sample of model families (dense image, transformer, sparse/FM, and the
LoD-feed lstm path) builds + trains a few tiny steps and emits the
one-line JSON metric, so the runner cannot bit-rot between bench rounds.
(The remaining models share the same feed builders; running all 12 here
would cost minutes of suite time for little extra coverage.)"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUNNER = os.path.join(ROOT, "benchmarks", "fluid_benchmark.py")


def _run(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [ROOT] + [p for p in (os.environ.get("PYTHONPATH"),) if p]))
    out = subprocess.run(
        [sys.executable, RUNNER] + args, env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = out.stdout.strip().splitlines()[-1]
    return json.loads(line)


@pytest.mark.parametrize("model", ["mnist", "transformer", "deepfm",
                                   "stacked_dynamic_lstm"])
def test_runner_emits_metric(model):
    res = _run(["--model", model, "--batch_size", "4", "--iters", "2"])
    assert res["model"] == model
    assert res["value"] > 0 and res["unit"]


def test_runner_real_data_mode():
    res = _run(["--model", "mnist", "--batch_size", "4", "--iters", "2",
                "--real_data"])
    assert res["value"] > 0
