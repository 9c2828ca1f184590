"""The jax-free process bootstrap: where the compile cache goes, and that
the DP kernels' short compiles are actually written to it."""
import os
import subprocess
import sys

import pytest

from repro import hostdev

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("env,want", [
    ("/somewhere/else/cache", "/somewhere/else/cache"),  # set: it wins
    (None, os.path.join(REPO, ".jax_cache")),            # unset: in checkout
    ("", os.path.join(REPO, ".jax_cache")),
])
def test_compile_cache_dir(env, want, monkeypatch):
    # setenv first so that monkeypatch restores both variables afterwards,
    # also where they were unset before the test
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env or "")
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "")
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS")
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert hostdev.ensure_compile_cache() == want
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want
    assert os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "0"
    assert hostdev.DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")


_CHILD = """
import sys
from repro.hostdev import ensure_compile_cache
ensure_compile_cache()
import jax, jax.numpy as jnp
jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()
"""


def test_short_compiles_are_written(tmp_path):
    """A sub-second compile still lands in the persistent cache."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", None)
    subprocess.run([sys.executable, "-c", _CHILD], env=env, check=True,
                   timeout=120)
    assert any(f.startswith("jit_") for f in os.listdir(tmp_path))
