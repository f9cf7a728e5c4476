"""Process-level JAX set-up of the entry points (``repro.launch.jaxenv``)."""
import os

import jax
import pytest

from repro.launch import jaxenv


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_wins(monkeypatch, cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    before = jax.config.jax_compilation_cache_dir
    assert jaxenv.use_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_default_is_fixed_repo_path(monkeypatch,
                                                  cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = jaxenv.use_compile_cache()
    assert path == str(jaxenv.CACHE_DIR)
    assert jaxenv.CACHE_DIR.name == ".jax_cache"
    assert (jaxenv.CACHE_DIR.parent / "pyproject.toml").exists()
    assert jax.config.jax_compilation_cache_dir == path


@pytest.mark.parametrize("platforms,applies", [("cpu", True), ("tpu", False),
                                               (None, False)])
def test_virtual_devices_only_on_cpu(monkeypatch, platforms, applies):
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    monkeypatch.setenv("XLA_FLAGS", "")
    assert jaxenv.virtual_cpu_devices(4) is applies
    flags = os.environ["XLA_FLAGS"]
    assert ("--xla_force_host_platform_device_count=4" in flags) is applies


def test_virtual_devices_keep_callers_count(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=2")
    assert jaxenv.virtual_cpu_devices(8)
    assert os.environ["XLA_FLAGS"] == \
        "--xla_force_host_platform_device_count=2"
