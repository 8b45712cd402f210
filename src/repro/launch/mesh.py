"""Production meshes.  Functions only — importing this never touches jax
device state; callers (dryrun) are responsible for the 512-device env."""
from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types (sharding propagated by the
    compiler, as every call site here expects)."""
    auto = (jax.sharding.AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(axis_shapes, axis_names, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n_data: int = 1, n_model: int = 1):
    """Mesh over this process's devices: host devices in tests and bench
    subprocesses, chips on a TPU host."""
    return make_mesh((n_data, n_model), ("data", "model"))
