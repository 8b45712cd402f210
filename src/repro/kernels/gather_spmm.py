"""Pallas TPU kernel: fused index-gather + SPMM (Deal §3.5, Fig 13).

The plain ``spmm`` kernel assumes its neighbor ids index the feature
table directly.  Real pipelines rarely have that luxury: the feature
loader leaves rows in file order (§3.5 feature preparation) and delta
refresh gathers a compacted universe of rows (``gnnserve.delta``), so
both paths historically materialized a reordered copy — ``rows[table]``
in ``feature_prep.fused_load``, a dense ``searchsorted`` remap of every
neighbor matrix in ``delta``.  This kernel consumes the feature table
AND the row-index table directly:

    out[i] = sum_f w[i,f] * mask[i,f] * h[table[nbr[i,f]]]

i.e. the reorder disappears into layer-1's gather: one extra scalar
DMA per edge (the table entry) replaces an (N, D) HBM round-trip.
Per grid step the ``nbr`` tile is staged in SMEM; ``h`` and ``table``
stay in HBM (memory_space ANY).  A DMA moves whole 128-lane tiles, so
the table is laid out (ceil(N/128), 128) and each edge copies the tile
holding its entry into SMEM, a few rows of the block at a time, and
reads the entry from it as a scalar.  Then each edge's ``h`` row slice
is copied into VMEM exactly as in ``spmm``, and the weighted sum is
``spmm``'s, so the fused kernel is bitwise equal to ``spmm`` over a
materialized reorder.  Validated in interpret mode against
``ref.gather_spmm_ref``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spmm import (LANES, auto_block_n, clamp_id,
                                edge_specs, gather_rows, lane_block,
                                pad_lanes, weighted_sum)


def _gather_spmm_kernel(nbr_ref, w_ref, table_hbm, h_hbm, o_ref, tbl_ref,
                        idx_ref, rows_ref, sem, *, block_d: int,
                        fanout: int, block_n: int, n_ids: int, chunk: int):
    d0 = pl.program_id(1) * block_d

    def gid(r, f):
        return clamp_id(nbr_ref[r, f], n_ids)

    def copy(j, f, g):      # the lane tile holding table[g]
        return pltpu.make_async_copy(
            table_hbm.at[pl.ds(g // LANES, 1)],
            tbl_ref.at[pl.ds(f * chunk + j, 1)], sem)

    def resolve(c, carry):  # fused indirection, ``chunk`` rows at a time
        r0 = c * chunk
        for j in range(chunk):
            for f in range(fanout):
                copy(j, f, gid(r0 + j, f)).start()
        for j in range(chunk):
            for f in range(fanout):
                copy(j, f, 0).wait()
        for j in range(chunk):
            for f in range(fanout):
                idx_ref[r0 + j, f] = tbl_ref[f * chunk + j,
                                             gid(r0 + j, f) % LANES]
        return carry

    jax.lax.fori_loop(0, block_n // chunk, resolve, 0)
    gather_rows(h_hbm, rows_ref, sem, lambda r, f: idx_ref[r, f],
                block_n=block_n, fanout=fanout, col0=d0, width=block_d)
    o_ref[...] = weighted_sum(w_ref, rows_ref, fanout=fanout)


@functools.partial(jax.jit, static_argnames=("block_n", "block_d",
                                             "interpret"))
def gather_spmm(h, table, w, nbr, mask, *, block_n: int = None,
                block_d: int = 128, interpret: bool):
    """out[i] = sum_f w[i,f]*mask[i,f]*h[table[nbr[i,f]]].

    h: (U, D) source-row table in ARBITRARY order; table: (N,) int map
    from the id space ``nbr`` uses onto h's rows; w/mask/nbr: (R, F).
    Same R/U decoupling as ``spmm`` (row-subset mode), with the id
    translation fused into the gather.  R % block_n == 0; any D, padded
    to lane tiles as in ``spmm``.  Masked slots may map anywhere in-range
    (their coefficient is 0.0 exactly).
    """
    U, D = h.shape
    R, F = nbr.shape
    if block_n is None:
        block_n = auto_block_n(R)
    assert R % block_n == 0, (R, block_n)
    wm = (w * mask).astype(h.dtype).astype(jnp.float32)
    table = jnp.asarray(table, jnp.int32)
    n_ids = table.shape[0]
    chunk = math.gcd(block_n, 8)
    hp = pad_lanes(h.astype(jnp.float32))
    Dp = hp.shape[1]
    block_d = lane_block(D, block_d)
    out = pl.pallas_call(
        functools.partial(_gather_spmm_kernel, block_d=block_d, fanout=F,
                          block_n=block_n, n_ids=n_ids, chunk=chunk),
        grid=(R // block_n, Dp // block_d),
        in_specs=edge_specs(block_n, F) + [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((block_n, block_d), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, Dp), jnp.float32),
        scratch_shapes=[pltpu.SMEM((F * chunk, LANES), jnp.int32),
                        pltpu.SMEM((block_n, F), jnp.int32),
                        pltpu.VMEM((F, block_n, block_d), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
    )(jnp.asarray(nbr, jnp.int32), wm,
      pad_lanes(table[None]).reshape(-1, LANES), hp)
    return out[:, :D].astype(h.dtype)
