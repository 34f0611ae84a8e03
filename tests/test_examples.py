"""Every example in examples/ runs to completion on CPU — the scripts
are the 'switching user's' first contact; they must never rot."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = sorted(
    f for f in os.listdir(os.path.join(REPO, "examples"))
    if f.endswith(".py"))


@pytest.mark.parametrize("script", EXAMPLES)
def test_example_runs(script):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script)],
        capture_output=True, text=True, timeout=420, env=env,
        cwd=REPO)
    assert out.returncode == 0, (script, out.stdout[-1500:],
                                 out.stderr[-1500:])
