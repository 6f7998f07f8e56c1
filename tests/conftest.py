"""Shared fixtures."""

import jax
import pytest


@pytest.fixture
def compile_cache_dir(tmp_path, monkeypatch):
    """A private persistent compile cache for a test that runs serve.main.

    serve.main turns JAX's persistent cache on for the whole process. Left
    at the checkout's shared directory, two test workers could read an
    entry while another writes it; so each test gets its own directory,
    and the process's cache settings are put back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    saved = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    path = tmp_path / "jax_cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(path))
    yield path
    for name, value in saved.items():
        jax.config.update(name, value)
    cc.reset_cache()
