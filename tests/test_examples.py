"""Every example under examples/ must run green (CPU backend, subprocess) —
they are the user-facing counterpart of the reference's src/ test programs
and each self-checks against an oracle."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = sorted(f for f in os.listdir(os.path.join(REPO, "examples"))
                  if f.endswith(".py") and not f.startswith("_"))


@pytest.mark.parametrize("name", EXAMPLES)
def test_example_runs(name):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "examples", name)],
                          capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, f"{name} failed:\n{proc.stdout}\n{proc.stderr}"
    assert "OK" in proc.stdout
