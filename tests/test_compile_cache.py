"""Placement of JAX's persistent compile cache by the entry points."""
import os

import jax
import pytest

from repro import compile_cache
from repro.compile_cache import CACHE_ENV, enable_compile_cache


@pytest.fixture()
def restore_jax_cache_config():
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    was = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in was.items():
        jax.config.update(n, v)


def test_library_import_leaves_the_cache_alone():
    import repro.core.refexec  # noqa: F401
    import repro.core.simulator  # noqa: F401
    import repro.serve.engine  # noqa: F401
    assert jax.config.jax_compilation_cache_dir == os.environ.get(CACHE_ENV)


def test_cache_goes_to_the_checkout_or_the_env_dir(
        tmp_path, monkeypatch, restore_jax_cache_config):
    assert os.path.isdir(os.path.join(compile_cache.CHECKOUT, "src",
                                      "repro"))
    monkeypatch.setattr(compile_cache, "CHECKOUT", str(tmp_path))
    monkeypatch.delenv(CACHE_ENV, raising=False)
    path = enable_compile_cache()
    assert path == str(tmp_path / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0

    # a directory given from outside is JAX's to read: no path set in code
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv(CACHE_ENV, str(tmp_path / "outside"))
    assert enable_compile_cache() == str(tmp_path / "outside")
    assert jax.config.jax_compilation_cache_dir is None
