"""Where the entry points keep JAX's persistent compilation cache."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch.cache import DEFAULT_CACHE_DIR, use_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_default_is_the_checkouts_ignored_cache_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert use_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


def test_environment_dir_is_left_alone(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was


def test_entries_land_only_in_the_environment_dir(tmp_path):
    """A fresh process with the variable set writes its compiled entries
    there, and none into the checkout's default directory."""
    before = (set(os.listdir(DEFAULT_CACHE_DIR))
              if DEFAULT_CACHE_DIR.exists() else set())
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.cache import use_compile_cache\n"
        "use_compile_cache()\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n"
    )
    env = {**os.environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
           "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(ROOT / "src"),
                                          os.environ.get("PYTHONPATH", "")])}
    subprocess.run([sys.executable, "-c", script], env=env, check=True,
                   timeout=120)
    assert any(name.endswith("-cache") for name in os.listdir(tmp_path))
    after = (set(os.listdir(DEFAULT_CACHE_DIR))
             if DEFAULT_CACHE_DIR.exists() else set())
    assert after == before
