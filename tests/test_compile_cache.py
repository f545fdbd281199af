"""The persistent compilation cache the entry points share."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro import compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_env_dir_receives_compiled_programs(tmp_path):
    # a fresh process, as an entry point starts: JAX reads the variable
    # itself and the helper sets no directory of its own
    code = ("from repro import compile_cache; import jax; "
            "d = compile_cache.enable(); "
            "assert jax.config.jax_compilation_cache_dir == d, d; "
            "jax.jit(lambda x: x * 2 + 1)(3.0).block_until_ready(); "
            "print(d)")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=str(REPO / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir()), "no compiled program was cached"


def test_default_dir_is_the_repo_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable()
        assert got == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_wins_without_a_config_write(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
