"""Where the persistent compile cache lives (``repro.launch.cache``)."""
from pathlib import Path

import jax
import pytest

from repro.launch.cache import CHECKOUT_CACHE_DIR, enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing is set in code;
    without it the cache is the fixed, git-ignored ``.jax_cache/`` at the
    checkout root."""
    was = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = enable_compile_cache()
        if from_env:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == was
        else:
            assert got == str(ROOT / ".jax_cache") == str(CHECKOUT_CACHE_DIR)
            assert jax.config.jax_compilation_cache_dir == got
            assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
