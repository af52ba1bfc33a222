"""Entry-point contracts that need a fresh process: the chip smoke test
refuses to run without a TPU, and the compilation cache is placed where
``JAX_COMPILATION_CACHE_DIR`` says, else at ``<repo>/.jax_cache``."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(args, **env_over):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(REPO / "src"), env.get("PYTHONPATH", "")) if p)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_over)
    return subprocess.run([sys.executable, *args], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_without_tpu():
    out = _run(["chip_smoke.py"])
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


@pytest.mark.parametrize("env_dir", [None, "placed"])
def test_compile_cache_placement(env_dir, tmp_path):
    over = {}
    if env_dir:
        over["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    out = _run(["-c", "import json, jax; "
                "from repro.compile_cache import use_compile_cache; "
                "print(json.dumps([use_compile_cache(), "
                "jax.config.jax_compilation_cache_dir]))"], **over)
    assert out.returncode == 0, out.stderr
    used, configured = json.loads(out.stdout.strip().splitlines()[-1])
    want = str(tmp_path / env_dir) if env_dir else str(REPO / ".jax_cache")
    assert used == configured == want
