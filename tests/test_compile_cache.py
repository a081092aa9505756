"""`repro.compile_cache`: where the persistent compile cache goes."""
import os

import pytest

from repro import compile_cache


@pytest.fixture()
def updates(monkeypatch):
    """Record jax.config.update calls instead of changing this process."""
    calls = {}
    monkeypatch.setattr(
        compile_cache.jax.config, "update",
        lambda name, value: calls.__setitem__(name, value),
    )
    return calls


def test_env_var_wins_and_no_other_dir_is_set(monkeypatch, updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in updates
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_default_is_a_fixed_dir_in_the_checkout(monkeypatch, updates, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    path = compile_cache.enable_compile_cache()
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(checkout, ".jax_cache")
    assert updates["jax_compilation_cache_dir"] == path
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
    # Same answer on every call: nothing in it depends on time or pid.
    assert compile_cache.enable_compile_cache() == path


def test_importing_the_package_enables_nothing():
    import importlib
    import pkgutil

    import jax

    import repro

    for mod in pkgutil.walk_packages(repro.__path__, "repro."):
        importlib.import_module(mod.name)
    assert jax.config.jax_compilation_cache_dir == os.environ.get(
        compile_cache.ENV_VAR
    )
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 1.0
