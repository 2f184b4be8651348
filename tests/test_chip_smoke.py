"""``chip_smoke.py`` off the chip: the CPU rehearsal of every phase, the
refusal to run without a TPU, and where the compile cache goes.

All slow-marked: each case is a fresh interpreter (the script owns its
process, as it does on the chip), and the trainer rehearsal compiles
ResNet50 for CPU.
"""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env_extra, cwd=REPO, timeout=1500):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_rehearsal_runs_every_phase(tmp_path):
    cache = tmp_path / "cache"
    before = os.path.exists(os.path.join(REPO, ".xla_cache"))
    res = _run(["chip_smoke.py", "--rehearse"],
               {"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
                "JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    summary = json.loads(res.stdout.strip().splitlines()[-1])
    assert summary["ok"] is True and summary["rehearsal"] is True
    assert summary["device"] == {"platform": "cpu", "kind": "cpu",
                                 "count": 4}
    assert list(summary["phases"]) == ["trainer", "server", "kernels",
                                       "multichip"]
    # the variable was set: the cache is there and nowhere else
    assert any(cache.iterdir())
    assert os.path.exists(os.path.join(REPO, ".xla_cache")) == before


def test_default_invocation_refuses_without_a_tpu():
    res = _run(["chip_smoke.py"], {})
    assert res.returncode not in (0, None)
    # a refusal prints no result: stdout stays empty
    assert res.stdout.strip() == "", res.stdout


@pytest.mark.parametrize("env_dir", [None, "/some/dir"])
def test_compile_cache_location(env_dir):
    code = ("import jax, deeplearning4j_tpu as d;"
            "print(d.enable_compile_cache());"
            "print(jax.config.jax_compilation_cache_dir)")
    res = _run(["-c", code], {} if env_dir is None
               else {"JAX_COMPILATION_CACHE_DIR": env_dir})
    assert res.returncode == 0, res.stderr[-2000:]
    returned, configured = res.stdout.strip().splitlines()[-2:]
    if env_dir is None:
        # unset: the code points jax at <checkout>/.xla_cache
        assert returned == configured == os.path.join(REPO, ".xla_cache")
    else:
        # set: the code sets nothing; jax read the variable itself
        assert returned == configured == env_dir
