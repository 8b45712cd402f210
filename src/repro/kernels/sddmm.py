"""Pallas TPU kernel: SDDMM over fanout neighbor matrices (GAT scoring).

e[i, f] = <q[i], k[nbr[i, f]]> — sampled dense-dense products where the
sparsity pattern is the fixed-fanout layer graph.  q is tiled (bn, D) in
VMEM and the ids (bn, F) in SMEM; k stays in HBM and each edge's row is
DMA'd into a (F, bn, D) VMEM scratch (``spmm.gather_rows``), with q and k
zero-padded to whole lane tiles.  Slot f's
dots for the whole block are one lane reduction of q * k_f, placed into
column f of the (bn, F) score tile.  Validated in interpret mode vs
ref.sddmm_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spmm import (auto_block_n, edge_specs, gather_rows,
                                pad_lanes)


def _sddmm_kernel(nbr_ref, mask_ref, q_ref, k_hbm, o_ref, rows_ref, sem, *,
                  fanout: int, block_n: int):
    gather_rows(k_hbm, rows_ref, sem, lambda r, f: nbr_ref[r, f],
                block_n=block_n, fanout=fanout, width=k_hbm.shape[1])
    q = q_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
    s = jnp.zeros(o_ref.shape, jnp.float32)
    for f in range(fanout):
        dot = jnp.sum(q * rows_ref[f], axis=1, keepdims=True)   # (bn, 1)
        s = jnp.where(lane == f, dot, s)
    o_ref[...] = s * mask_ref[...]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def sddmm(q, k, nbr, mask, *, block_n: int = None, interpret: bool):
    """q: (N, D); k: (U, D) source table; nbr, mask: (N, F) with ids into
    k's rows (U and N decouple for row-subset execution).  Returns (N, F)
    f32 scores.  block_n=None picks the largest divisor of N <=64 —
    the old fixed block_n=8 launched 8x more grid steps than needed on
    typical pow2-padded row counts."""
    N, D = q.shape
    F = nbr.shape[1]
    if block_n is None:
        block_n = auto_block_n(N)
    assert N % block_n == 0, (N, block_n)
    mask_f = mask.astype(jnp.float32)
    # zero lanes add exactly 0.0 to every dot
    q = pad_lanes(q.astype(jnp.float32))
    k = pad_lanes(k.astype(jnp.float32))
    D = q.shape[1]
    return pl.pallas_call(
        functools.partial(_sddmm_kernel, fanout=F, block_n=block_n),
        grid=(N // block_n,),
        in_specs=edge_specs(block_n, F) + [
            pl.BlockSpec((block_n, D), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((block_n, F), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, F), jnp.float32),
        scratch_shapes=[pltpu.VMEM((F, block_n, D), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
        name="sddmm",  # the op name traces and rooflines match on
    )(jnp.asarray(nbr, jnp.int32), mask_f, q, k)
