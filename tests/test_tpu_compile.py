"""Compile the four GNN kernels for a described TPU v5e (no chip needed).

The TPU compiler is installed even where no chip is attached, so the
kernels are lowered with ``interpret=False`` against a ``v5e:2x2``
topology description and compiled at the chip smoke's widths: D=128
(ogbn-papers100M), 4 heads, fanout 8, a few thousand rows, plus
ogbn-products' D=100, which the kernels pad to a whole lane tile.  This
catches what interpret mode cannot: loads from HBM refs, scatter-adds on
carried values, blocks narrower than a lane tile.

The distributed GAT layer's shard_map programs (``core/primitives.py``)
are compiled on the described 2x2 mesh, p=2 x m=2 as the four-chip cell
runs them, with their pinned module names.

The topology is described inside a module fixture, never at import:
only one process may load the TPU runtime at a time, so each pytest
worker must collect these tests without touching it.  Keep every such
compile in this one file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gat_attention import gat_attention
from repro.kernels.gather_spmm import gather_spmm
from repro.kernels.sddmm import sddmm
from repro.kernels.spmm import spmm

N, F, HEADS = 4096, 8, 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # libtpu is a pinned requirement: failing to describe the chip is a
    # failure of these tests, never a skip
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to a persistent cache
    but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


W = ((N, F), jnp.float32)           # per-edge weights
IDS = ((N, F), jnp.int32)           # neighbor ids
MASK = ((N, F), jnp.bool_)
HEAD_W = ((HEADS, N, F), jnp.float32)   # head-major per-edge weights


@pytest.mark.parametrize("D,dtype", [(128, jnp.float32), (100, jnp.float32),
                                     (128, jnp.bfloat16)])
def test_spmm_compiles(D, dtype, one_chip, no_persistent_cache):
    def fn(h, w, nbr, mask):
        return spmm(h, w, nbr, mask, block_n=64, block_d=128,
                    interpret=False)
    c = _compile(fn, [((N, D), dtype), W, IDS, MASK], one_chip)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("D", [128, 100])
def test_gather_spmm_compiles(D, one_chip, no_persistent_cache):
    def fn(h, table, w, nbr, mask):
        return gather_spmm(h, table, w, nbr, mask, block_n=64,
                           block_d=128, interpret=False)
    c = _compile(fn, [((N, D), jnp.float32), ((N,), jnp.int32), W, IDS,
                      MASK], one_chip)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("D", [128, 100])
def test_spmm_head_major_compiles(D, one_chip, no_persistent_cache):
    def fn(h, w, nbr, mask):
        return spmm(h, w, nbr, mask, block_n=64, block_d=128,
                    interpret=False)
    c = _compile(fn, [((N, D), jnp.float32), HEAD_W, IDS, MASK], one_chip)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("D", [128, 100])
def test_gather_spmm_head_major_compiles(D, one_chip, no_persistent_cache):
    def fn(h, table, w, nbr, mask):
        return gather_spmm(h, table, w, nbr, mask, block_n=64,
                           block_d=128, interpret=False)
    c = _compile(fn, [((N, D), jnp.float32), ((N,), jnp.int32), HEAD_W,
                      IDS, MASK], one_chip)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("D", [128, 128 // HEADS])
def test_sddmm_compiles(D, one_chip, no_persistent_cache):
    fn = functools.partial(sddmm, block_n=64, interpret=False)
    c = _compile(fn, [((N, D), jnp.float32), ((N, D), jnp.float32), IDS,
                      MASK], one_chip)
    assert "tpu_custom_call" in c.as_text()


def test_gat_attention_compiles(one_chip, no_persistent_cache):
    fn = functools.partial(gat_attention, heads=HEADS, block_n=64,
                           interpret=False)
    c = _compile(fn, [((N, 128), jnp.float32), ((2 * N, 128), jnp.float32),
                      IDS, MASK], one_chip)
    assert "tpu_custom_call" in c.as_text()


# The names a device trace shows for each kernel: the jitted wrapper's
# module (``jit_<name>``) and the Pallas custom call inside it (``<name>``,
# set by ``pallas_call(name=...)``).  The benchmark's roofline metrics
# match on these, so a rename must be deliberate.  A case ``<name>.<how>``
# pins ``<name>`` for another call of the same kernel.
PINNED = {
    "spmm": (spmm, [((N, 128), jnp.float32), W, IDS, MASK],
             dict(block_n=64, block_d=128)),
    "gather_spmm": (gather_spmm, [((N, 128), jnp.float32),
                                  ((N,), jnp.int32), W, IDS, MASK],
                    dict(block_n=64, block_d=128)),
    "spmm.head_major": (spmm, [((N, 128), jnp.float32), HEAD_W, IDS, MASK],
                        dict(block_n=64, block_d=128)),
    "sddmm": (sddmm, [((N, 128), jnp.float32), ((N, 128), jnp.float32),
                      IDS, MASK], dict(block_n=64)),
    "gat_attention": (gat_attention, [((N, 128), jnp.float32),
                                      ((2 * N, 128), jnp.float32), IDS,
                                      MASK], dict(heads=HEADS, block_n=64)),
}


@pytest.mark.parametrize("case", sorted(PINNED))
def test_kernel_names_are_pinned(case, one_chip, no_persistent_cache):
    kernel, shapes, kw = PINNED[case]
    name = case.split(".")[0]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = kernel.lower(*args, interpret=False, **kw).compile().as_text()
    assert re.search(rf"^HloModule jit_{name}\b", text, re.M)
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls and all(
        re.match(rf"\s*(ROOT )?%{name}(\.\d+)? = ", ln) for ln in calls), \
        calls


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))


def _dist_programs(mesh):
    """name -> (jitted, arg shapes with specs, collective it must hold)
    for one dist GAT layer.  The head-major spmm is compiled inside a
    caller's jit, as the executor's eager call would not name it."""
    from jax.sharding import PartitionSpec as P
    from repro.core import primitives as prim
    R, E = 512, 4096
    hd = ((N, 128), jnp.float32, P("data", "model"))
    plan = [((2, 2, R), jnp.int32, P("data", None, None))] + \
        [((2, 2, E), jnp.int32, P("data", None, None))] * 3 + \
        [((2, 2, E), jnp.bool_, P("data", None, None))]
    mask = ((N, F), jnp.float32, P("data", None))
    alpha = ((N, F, HEADS), jnp.float32, P("data", None, "model"))
    spmm = prim.make_spmm_p(mesh, 2)
    return {
        "dist_gemm": (prim.make_gemm(mesh),
                      [hd, ((128, 128), jnp.float32, P(None, None))],
                      "all-to-all"),
        "dist_gat_attention": (
            prim.make_gat_attention_p(mesh, 2, F, HEADS), [hd, hd, mask]
            + plan, "collective-permute"),
        "dist_spmm.head_major": (jax.jit(lambda h, a, *p: spmm(h, a, *p)),
                                 [hd, alpha] + plan, "collective-permute"),
    }


@pytest.mark.parametrize("case", ["dist_gemm", "dist_gat_attention",
                                  "dist_spmm.head_major"])
def test_dist_gat_layer_compiles_on_a_2x2_mesh(case, mesh_2x2,
                                               no_persistent_cache):
    from jax.sharding import NamedSharding
    fn, shapes, collective = _dist_programs(mesh_2x2)[case]
    args = [jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(mesh_2x2, p))
            for s, d, p in shapes]
    text = fn.lower(*args).compile().as_text()
    assert collective in text
    if "." not in case:
        assert re.search(rf"^HloModule jit_{case}\b", text, re.M)
