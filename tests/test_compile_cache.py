"""The persistent compile cache goes where it is told, and only there.

Each placement runs in its own subprocess: jax initializes the cache once
per process, and enabling it here would leak into every later test."""
import os
import subprocess
import sys
from pathlib import Path

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]

_SCRIPT = r"""
import sys
import jax, jax.numpy as jnp
from repro import compile_cache
if len(sys.argv) > 1:                      # stand-in for the checkout path
    compile_cache.DEFAULT_DIR = type(compile_cache.DEFAULT_DIR)(sys.argv[1])
print("CACHE", compile_cache.enable_compile_cache())
jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.arange(8.0)).block_until_ready()
"""


def _run(env_dir, default_dir):
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    if env_dir is not None:
        env[compile_cache.ENV_VAR] = str(env_dir)
    res = subprocess.run([sys.executable, "-c", _SCRIPT, str(default_dir)],
                         env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    return res.stdout


def test_default_cache_dir_is_checkout_local():
    assert compile_cache.DEFAULT_DIR == ROOT / ".jax_cache"


def test_env_var_wins_and_default_is_used_otherwise(tmp_path):
    from_env, default = tmp_path / "from_env", tmp_path / "default"
    out = _run(from_env, default)
    assert f"CACHE {from_env}" in out
    assert any(from_env.iterdir()) and not default.exists()

    from_env_files = set(from_env.iterdir())
    out = _run(None, default)
    assert f"CACHE {default}" in out
    assert any(default.iterdir())
    assert set(from_env.iterdir()) == from_env_files
