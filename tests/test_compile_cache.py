"""Where the persistent compilation cache goes: ``JAX_COMPILATION_CACHE_DIR``
when the environment sets it, else ``.jax_cache/`` at the checkout root."""

import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, configure_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_checkout_cache_dir_without_env(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert configure_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_env_cache_dir_receives_the_cache(tmp_path):
    """A fresh process with the variable set: the helper sets nothing, and
    a compile lands in that directory."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import configure_compile_cache\n"
        "print(configure_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64)))"
        ".block_until_ready()\n"
    )
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"),
                                           os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [str(tmp_path), str(tmp_path)]
    assert any(tmp_path.iterdir()), "no cache entry was written"
