"""Where the entry points put JAX's persistent compilation cache."""

import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[2]


def test_default_dir_is_fixed_and_git_ignored(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert Path(got) == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()


def test_env_dir_is_left_to_jax_and_written(tmp_path):
    cache = tmp_path / "cache"
    prog = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == str(cache)
    assert any(cache.iterdir())
