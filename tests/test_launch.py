"""Entry-point set-up: where the persistent compilation cache lives, and
``chip_smoke.py`` refusing to report success without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CACHE_PROBE = """
import json, jax
from repro import compat
path = compat.setup_compilation_cache()
print(json.dumps({"path": str(path),
                  "config": jax.config.jax_compilation_cache_dir,
                  "min_secs": jax.config.jax_persistent_cache_min_compile_time_secs}))
"""


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu",
               **extra)
    return env


@pytest.mark.parametrize("from_env", [True, False])
def test_compilation_cache_dir(tmp_path, from_env):
    """``JAX_COMPILATION_CACHE_DIR`` wins whenever it is set; otherwise the
    cache sits at the fixed ``<repo>/.jax_cache``."""
    want = str(tmp_path / "xla") if from_env else \
        os.path.join(REPO, ".jax_cache")
    env = _env(JAX_COMPILATION_CACHE_DIR=want) if from_env else _env()
    r = subprocess.run([sys.executable, "-c", CACHE_PROBE], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out == {"path": want, "config": want, "min_secs": 0}
    assert os.path.isdir(want)


@pytest.mark.parametrize("value", ["", None])
def test_compilation_cache_dir_default(monkeypatch, value):
    """An empty ``JAX_COMPILATION_CACHE_DIR`` counts as unset."""
    from repro import compat
    if value is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", value)
    assert compat.compilation_cache_dir() == compat.REPO_ROOT / ".jax_cache"


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_fails_without_a_tpu(tmp_path, alone):
    """On the CPU, and as a lone file without the rest of the repo, the
    smoke script exits non-zero and never prints its success line."""
    script = os.path.join(REPO, "chip_smoke.py")
    cwd = REPO
    if alone:
        cwd = str(tmp_path)
        script = shutil.copy(script, cwd)
    r = subprocess.run([sys.executable, script], cwd=cwd, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
