"""Persistent compilation cache placement (repro.launch.compile_cache):
the environment's directory when JAX_COMPILATION_CACHE_DIR is set, else
one fixed directory inside the checkout."""

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    old = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in old.items():
        jax.config.update(k, v)


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_cache_directory(monkeypatch, tmp_path, restore_config, from_env):
    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.use_compile_cache()
    if from_env:
        assert path == str(tmp_path)
        # JAX reads the variable itself: the code sets no directory
        assert jax.config.jax_compilation_cache_dir == before
    else:
        assert path == str(compile_cache.REPO_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert (compile_cache.REPO_ROOT / "src" / "repro").is_dir()
    # the UCT kernels compile in about a second: nothing is kept out
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
