"""Entry-point set-up: the persistent compile cache and the HBM budget."""

import jax
import pytest

from repro.launch import runtime


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv(runtime.CACHE_DIR_ENV, raising=False)
    path = runtime.enable_compile_cache()
    assert path == str(runtime.DEFAULT_CACHE_DIR)
    assert runtime.DEFAULT_CACHE_DIR.name == ".jax_cache"
    assert (runtime.DEFAULT_CACHE_DIR.parent / "src" / "repro").is_dir()
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_env_sets_nothing(monkeypatch, cache_dir_config, tmp_path):
    monkeypatch.setenv(runtime.CACHE_DIR_ENV, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None   # JAX reads the env


class _Dev:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("stats", [None, {}, {"bytes_in_use": 5}])
def test_hbm_budget_unknown_without_a_limit(stats):
    assert runtime.device_hbm_budget(_Dev(stats)) is None


def test_hbm_budget_is_limit_less_margin():
    limit = 16 * 2**30
    assert runtime.device_hbm_budget(_Dev({"bytes_limit": limit})) == \
        limit - runtime.ACTIVATION_MARGIN_BYTES
