"""API.spec freshness gate (reference keeps paddle/fluid/API.spec in CI
for exactly this): the committed surface listing must match what the
package actually exports."""
from __future__ import annotations

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_api_spec_is_current():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "gen_api_spec.py"), "--check"],
        cwd=REPO,
        capture_output=True,
        text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        timeout=300,
    )
    assert r.returncode == 0, r.stdout + r.stderr
