"""The persistent compilation cache is placed from outside: where
``JAX_COMPILATION_CACHE_DIR`` says, else at ``<repo>/.jax_cache``.
Each case runs in a fresh interpreter so this process's JAX config is
left alone."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

PROBE = """
import jax, jax.numpy as jnp
from repro.compile_cache import use_compile_cache
print(use_compile_cache())
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
"""


def _probe(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    else:
        # a miss-only cache: nothing is compiled into the repo here
        env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    out = subprocess.run([sys.executable, "-c", PROBE], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True)
    return out.stdout.split()


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placed_from_outside(tmp_path, from_env):
    if from_env:
        returned, configured = _probe(tmp_path)
        assert returned == configured == str(tmp_path)
        assert any(tmp_path.iterdir()), "nothing cached where env says"
    else:
        returned, configured = _probe(None)
        assert returned == configured == str(REPO / ".jax_cache")
