"""The shipped examples must actually run (reference keeps its
``examples/`` compiling and drives them in integration tests)."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
@pytest.mark.parametrize("script", [
    "basic_operations.py", "multi_mount.py", "jax_training_pipeline.py",
])
def test_example_runs_self_contained(script):
    if script == "jax_training_pipeline.py":
        pytest.importorskip("jax")
        pytest.importorskip("optax")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", script)],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-1500:]
    assert "done." in r.stdout or "loader HBM stats" in r.stdout
