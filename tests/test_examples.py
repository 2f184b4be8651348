"""Every example script must run end-to-end in smoke mode (the
dl4j-examples role: runnable documentation — broken examples are worse
than none)."""

import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = sorted(f for f in os.listdir(os.path.join(REPO, "examples"))
                  if f.endswith(".py"))

# slow: the three heaviest example smokes (~11-20s each); the subsystems
# they demonstrate have dedicated tier-1 modules (test_model_sharding.py/
# test_parallel.py, test_generation.py/test_zoo.py, test_modelimport.py)
# — see the tier-1 duration budget note in conftest.py
_SLOW_EXAMPLES = {"lenet_mesh_dataparallel.py",
                  "transformer_text_generation.py",
                  "keras_residual_import.py"}


@pytest.mark.parametrize(
    "script",
    [pytest.param(s, marks=pytest.mark.slow) if s in _SLOW_EXAMPLES else s
     for s in EXAMPLES])
def test_example_runs(script):
    env = dict(os.environ, EXAMPLES_SMOKE="1")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script)],
        capture_output=True, text=True, timeout=150, env=env)
    assert r.returncode == 0, (script, r.stderr[-800:])
    # every example prints a progress sentinel — exit code 0 alone cannot
    # catch an example that silently trains zero steps
    m = re.search(r"TRAINED iterations: (\d+)", r.stdout)
    assert m, (script, "missing TRAINED sentinel", r.stdout[-400:])
    assert int(m.group(1)) > 0, (script, "example trained zero steps")
