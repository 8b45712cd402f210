"""Compile the four GNN kernels for a described TPU v5e (no chip needed).

The TPU compiler is installed even where no chip is attached, so the
kernels are lowered with ``interpret=False`` against a ``v5e:2x2``
topology description and compiled at the chip smoke's widths: D=128
(ogbn-papers100M), 4 heads, fanout 8, a few thousand rows, plus
ogbn-products' D=100, which the kernels pad to a whole lane tile.  This
catches what interpret mode cannot: loads from HBM refs, scatter-adds on
carried values, blocks narrower than a lane tile.

The topology is described inside a module fixture, never at import:
only one process may load the TPU runtime at a time, so each pytest
worker must collect these tests without touching it.  Keep every such
compile in this one file.
"""
import functools
import os

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
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # libtpu is a pinned requirement: failing to describe the chip is a
    # failure of these tests, never a skip
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
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
