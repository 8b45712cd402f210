"""Process-level JAX setup that the entry points call, never ``import repro``.

``enable_compile_cache``  JAX's persistent compilation cache.  A cache
    directory placed from outside (``JAX_COMPILATION_CACHE_DIR``) wins
    and is used as is; otherwise the cache lives at a fixed path inside
    the checkout, derived from this file, so every process of the
    checkout finds it again: cluster workers run in a temp directory,
    which makes a cwd-relative path miss.
``require_host_platform``  the refusal of a path that must run on the
    CPU where JAX found a chip: a mesh respawned onto forced host devices
    (``--xla_force_host_platform_device_count``, which are CPU devices),
    or cluster workers, which cannot get a chip this process holds.  It
    refuses instead of running on the CPU unannounced.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache for this process and
    return its directory.  Call before the first compile."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # op-by-op dispatch compiles many small programs: cache them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


FORCED_HOST = "runs a mesh of forced host devices, which are CPU devices"


def require_host_platform(what: str, *, why: str = FORCED_HOST,
                          fix: str = "run it with JAX_PLATFORMS=cpu, or "
                                     "run the chips' own path",
                          error: type = RuntimeError) -> None:
    """Raise ``error`` unless JAX's backend is the CPU.  The message
    reads "<what> <why>, but JAX found <backend> devices here; <fix>"."""
    import jax
    backend = jax.default_backend()
    if backend != "cpu":
        raise error(f"{what} {why}, but JAX found {backend} devices here; "
                    f"{fix}")
