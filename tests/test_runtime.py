"""Process-level setup of the entry points (``repro.runtime``): where the
persistent compilation cache lives, and the refusal to run a forced
host-device mesh where JAX found a chip."""
import os

import jax
import pytest

from repro import runtime


@pytest.fixture()
def restore_cache_config():
    """``enable_compile_cache`` sets process-wide JAX options; put them
    back so the rest of the suite stays cache-free."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_cache_dir_is_fixed_inside_checkout(monkeypatch,
                                            restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = runtime.enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert got == os.path.join(root, ".jax_cache")
    assert os.path.isabs(got)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_cache_dir_from_environment_wins(monkeypatch, tmp_path,
                                         restore_cache_config):
    outside = str(tmp_path / "cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    # JAX reads the variable itself; the helper must not override it
    jax.config.update("jax_compilation_cache_dir", outside)
    assert runtime.enable_compile_cache() == outside


@pytest.mark.parametrize("backend,refused", [("cpu", False), ("tpu", True)])
def test_forced_host_devices_refused_on_chip_host(monkeypatch, backend,
                                                  refused):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if refused:
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
            runtime.require_host_platform("bench")
    else:
        runtime.require_host_platform("bench")
