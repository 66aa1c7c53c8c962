"""The persistent compile cache lands where the environment says, else at a
fixed path inside the checkout (never one built from a temp name or a pid)."""

from pathlib import Path

import jax
import pytest

from repro.runtime import compile_cache as cc


@pytest.fixture
def restore_cache_config():
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])


def test_default_dir_is_fixed_inside_the_checkout():
    checkout = Path(__file__).resolve().parents[1]
    assert cc.DEFAULT_DIR == checkout / ".jax_cache"
    assert cc.compile_cache_dir({}) == str(checkout / ".jax_cache")
    assert cc.compile_cache_dir({cc.ENV_VAR: ""}) == str(cc.DEFAULT_DIR)


def test_environment_dir_wins():
    assert cc.compile_cache_dir({cc.ENV_VAR: "/cache/here"}) == "/cache/here"


def test_enable_leaves_a_set_environment_alone(monkeypatch, tmp_path,
                                               restore_cache_config):
    monkeypatch.setenv(cc.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cc.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_enable_uses_the_checkout_dir_when_unset(monkeypatch,
                                                 restore_cache_config):
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    assert cc.enable_compile_cache() == str(cc.DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == str(cc.DEFAULT_DIR)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
