"""The persistent compilation cache's directory: ``JAX_COMPILATION_CACHE_DIR``
wins; otherwise one fixed, git-ignored directory inside the checkout."""
from pathlib import Path

import jax

from repro.runtime.compile_cache import (CHECKOUT_CACHE_DIR, ENV_VAR,
                                         compile_cache_dir,
                                         enable_compile_cache)

ROOT = Path(__file__).resolve().parents[1]


def test_env_var_wins(monkeypatch):
    monkeypatch.setenv(ENV_VAR, "/elsewhere/cache")
    assert compile_cache_dir() == "/elsewhere/cache"


def test_default_is_fixed_and_git_ignored(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    assert compile_cache_dir() == str(ROOT / ".jax_cache")
    monkeypatch.setenv(ENV_VAR, "")
    assert compile_cache_dir() == str(CHECKOUT_CACHE_DIR)
    ignored = (ROOT / ".gitignore").read_text().split()
    assert f"/{CHECKOUT_CACHE_DIR.name}/" in ignored


def test_enable_sets_config_only_without_env(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(ENV_VAR, "/from/env")
        jax.config.update("jax_compilation_cache_dir", None)
        assert enable_compile_cache() == "/from/env"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv(ENV_VAR)
        assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == \
            str(CHECKOUT_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
