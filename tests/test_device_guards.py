"""Guards around the device: the chip smoke run refuses the CPU, and the
persistent compilation cache goes where the environment says, or to one
fixed directory inside the checkout."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parent.parent


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=300,
                       cwd=ROOT)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr


_CACHE_OPTIONS = ("jax_compilation_cache_dir",
                  "jax_persistent_cache_min_compile_time_secs",
                  "jax_persistent_cache_enable_xla_caches")


@pytest.fixture
def restore_cache_dir():
    before = {k: getattr(jax.config, k) for k in _CACHE_OPTIONS}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def test_compile_cache_respects_env(monkeypatch, tmp_path,
                                    restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no other directory
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.5
    assert jax.config.jax_persistent_cache_enable_xla_caches == "all"
    # a fixed path: calling again never moves it
    assert compile_cache.enable_compile_cache() == path
    gitignore = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in gitignore
