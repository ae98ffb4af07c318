"""Tier-1 wiring for the ``tools/check_<x>.py`` gates: each gate names its
scenarios once (``SCENARIOS``) and each is ONE counted case here, with the
scenario's name as its id, so a scenario that fails is one red test and the
others still run.

``tools/`` is no package: a gate is loaded by its path.  A scenario runs in
the worker's own process, so that the scenarios of one file share what the
process has already traced and compiled (one model object a gate:
``DecodeModel.step_programs``); ``apart`` names the ones that must have a
process of their own.  A gate run as a script is an operator's tool as before:
``python tools/check_<x>.py``.
"""
import functools
import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def tool_path(name):
    return os.path.join(REPO, "tools", name + ".py")


def load(name):
    spec = importlib.util.spec_from_file_location("_gate_" + name,
                                                  tool_path(name))
    mod = importlib.util.module_from_spec(spec)
    # a gate run as a script forces its own count of host devices before jax
    # starts; here jax has started (conftest.py), and what this process hands
    # to the processes it starts must stay conftest's
    flags = os.environ.get("XLA_FLAGS")
    try:
        spec.loader.exec_module(mod)
    finally:
        os.environ["XLA_FLAGS"] = flags
    return mod


def run_apart(name, scenario, timeout):
    """Scenario ``scenario`` of gate ``name`` in a clean process of its own;
    the line it printed."""
    code = ("import runpy; print(runpy.run_path(%r, run_name='gate')[%r]())"
            % (tool_path(name), scenario))
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, (
        "%s.%s failed:\nstdout:\n%s\nstderr:\n%s"
        % (name, scenario, proc.stdout, proc.stderr[-8000:]))
    return proc.stdout


def scenarios(name, apart=None):
    """Parametrize a test over gate ``name``'s scenarios: the test receives
    ``scenario``, calls it, and gets the line it would have printed.
    ``apart``: {scenario name: seconds of timeout, about three times what it
    takes} for the ones that run in a process of their own."""
    gate = load(name)
    runs = [functools.partial(run_apart, name, s.__name__,
                              apart[s.__name__])
            if s.__name__ in (apart or {}) else s for s in gate.SCENARIOS]
    return pytest.mark.parametrize("scenario", runs,
                                   ids=[s.__name__ for s in gate.SCENARIOS])
