"""Pallas TPU kernel: fanout-gather SPMM (the layer-graph aggregation).

The layer graphs of DEAL's all-node inference are fixed-fanout neighbor
matrices, so SPMM becomes "gather F rows per node, weighted-sum" — a
regular access pattern we tile as (node-block x feature-block).

Per grid step the (bn, F) neighbor-id tile is staged in SMEM, where ids
are scalars that can address DMAs; the (potentially huge) feature table
stays in HBM (memory_space ANY) and each edge's row slice is fetched
with ``pltpu.make_async_copy`` into a (F, bn, bd) VMEM scratch — all
bn*F copies in flight on one semaphore before the first wait.  The
weighted sum then runs on whole (bn, bd) tiles, adding the F neighbor
slots in order, so every row accumulates in the same per-row order at
any block size or row position (bitwise-stable across row subsets).
Head-major weights (heads, R, F) select each lane's head weight, so a
GAT attend gathers every row once for all heads (``weighted_sum``).

The kernels compute in f32 whatever the table dtype (the cast is exact
and the output is cast back), so the VMEM tiles keep the f32 (8, 128)
layout.  ``interpret`` has no default: ``kernels.ops`` decides it.
Validated in interpret mode against ``ref.spmm_ref``; compiled for a
described v5e in ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def auto_block_n(n: int) -> int:
    """Largest power-of-two node-block (<=64) that tiles n exactly.

    The kernels grid over n // block_n, so block_n must divide n; callers
    pad n to a multiple of 8 first (f32 sublane tile), which this floors
    to.  The row block of spmm / sddmm / gather_spmm / gat_attention
    when the caller passes none (``PallasExecutor`` never does).
    """
    for bn in (64, 32, 16, 8):
        if n % bn == 0:
            return bn
    for bn in (4, 2, 1):
        if n % bn == 0:
            return bn
    return 1


LANES = 128     # TPU vector lane width


def lane_width(d: int) -> int:
    """``d`` rounded up to whole 128-lane tiles."""
    return -(-d // LANES) * LANES


def pad_lanes(x):
    """Zero-pad the last axis to whole 128-lane tiles.  A table in HBM is
    laid out in lane tiles, and a DMA may only copy whole ones."""
    d = x.shape[-1]
    if lane_width(d) == d:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, lane_width(d) - d)])


def lane_block(d: int, block_d: int) -> int:
    """Column block of a kernel over ``d`` columns padded to
    ``lane_width(d)``.  Blocks are whole lane tiles, since the TPU refuses
    a narrower block of a wider array, so an awkward width (ogbn-products'
    D=100) runs padded to the next tile multiple."""
    bd = math.gcd(lane_width(d), block_d)
    return bd if bd % LANES == 0 else LANES


def clamp_id(idx, n: int):
    """Clamp a scalar row id into [0, n): the bounds rule of XLA's
    dynamic slices (and so of interpret mode), applied before the id
    addresses a DMA."""
    return jnp.minimum(jnp.maximum(idx, 0), n - 1)


def gather_rows(src_hbm, rows_ref, sem, row_id, *, block_n: int,
                fanout: int, col0=0, width: int):
    """DMA ``src_hbm[row_id(r, f), col0:col0 + width]`` into
    ``rows_ref[f, r]`` for every edge slot of the block, and return once
    every copy has landed.  ``row_id`` reads a scalar id (from SMEM);
    ``width`` is a whole number of lane tiles."""
    n = src_hbm.shape[0]

    def copy(r, f, idx):
        return pltpu.make_async_copy(
            src_hbm.at[pl.ds(idx, 1), pl.ds(col0, width)],
            rows_ref.at[f, pl.ds(r, 1)], sem)

    def start(r, carry):
        for f in range(fanout):
            copy(r, f, clamp_id(row_id(r, f), n)).start()
        return carry

    def wait(r, carry):
        for f in range(fanout):
            copy(r, f, 0).wait()
        return carry

    jax.lax.fori_loop(0, block_n, start, 0)
    jax.lax.fori_loop(0, block_n, wait, 0)


def column(tile, f: int):
    """Column ``f`` of a (bn, F) tile as a (bn, 1) vector.  Summing one
    value with zeros is exact, and it avoids an unaligned lane slice."""
    lane = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    return jnp.sum(jnp.where(lane == f, tile, 0.0), axis=1, keepdims=True)


def weighted_sum(w_ref, rows_ref, *, fanout: int, col0=0, dh: int = 0):
    """sum_f w[:, f] * rows[f], adding slot f = 0, 1, ... in order.

    With ``dh`` the (bn, heads * F) tile holds head k's slot-f weight in
    lane k * F + f (``edge_weights``), and lane c of the block takes head
    ``(col0 + c) // dh``'s: the weight is *selected* per lane, so every
    output element sees the same f32 multiply and add as a one-head call
    over its head's columns.  Lanes past heads * dh (lane padding) get
    no head and weight 0.0."""
    w = w_ref[...]
    shape = rows_ref.shape[1:]
    if not dh:
        def coef(f):
            return column(w, f)
    else:
        heads = w.shape[1] // fanout
        lane = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        owns = [(lane >= k * dh) & (lane < (k + 1) * dh)
                for k in range(heads)]

        def coef(f):
            tile = jnp.zeros(shape, jnp.float32)
            for k in range(heads):
                tile = jnp.where(owns[k], column(w, k * fanout + f), tile)
            return tile
    acc = jnp.zeros(shape, jnp.float32)
    for f in range(fanout):
        acc = acc + coef(f) * rows_ref[f]
    return acc


def _spmm_kernel(nbr_ref, w_ref, h_hbm, o_ref, rows_ref, sem, *,
                 block_d: int, fanout: int, block_n: int, dh: int):
    d0 = pl.program_id(1) * block_d
    gather_rows(h_hbm, rows_ref, sem, lambda r, f: nbr_ref[r, f],
                block_n=block_n, fanout=fanout, col0=d0, width=block_d)
    o_ref[...] = weighted_sum(w_ref, rows_ref, fanout=fanout, col0=d0,
                              dh=dh)


def edge_specs(block_n: int, fanout: int, w_width: int = None):
    """BlockSpecs of the (R, F) neighbor-id tile (SMEM, scalars that
    address DMAs) and of the (R, ``w_width`` or F) per-edge f32 weight
    tile (VMEM), for a grid whose first dim walks row blocks."""
    def index(i, *_):
        return (i, 0)
    return [pl.BlockSpec((block_n, fanout), index,
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block_n, w_width or fanout), index)]


def edge_weights(w, mask, h):
    """(wm, dh): the kernels' f32 per-edge weights w * mask, rounded
    through the table's dtype, and the head width over ``h``'s columns,
    0 for (R, F) weights.  Head-major (heads, R, F) weights go in as one
    (R, heads * F) array, head k's slot f in column k * F + f: as a
    (heads, R, F) operand every head's F slots would fill a 128-lane
    tile of HBM."""
    wm = (w * mask).astype(h.dtype).astype(jnp.float32)
    if w.ndim == 2:
        return wm, 0
    heads, R, F = w.shape
    D = h.shape[1]
    assert D % heads == 0, (D, heads)
    return wm.transpose(1, 0, 2).reshape(R, heads * F), D // heads


@functools.partial(jax.jit, static_argnames=("block_n", "block_d",
                                             "interpret"))
def spmm(h, w, nbr, mask, *, block_n: int = None, block_d: int = 128,
         interpret: bool):
    """out[i] = sum_f w[i,f]*mask[i,f]*h[nbr[i,f]].

    h: (N, D) source-row table; w/mask/nbr: (R, F).  The output has R rows
    — R and N are decoupled so the layer-op executors can gather from a
    universe table while producing only the target rows (row-subset mode).
    R % block_n == 0 (block_n=None picks the largest divisor <=64).  Any
    D: columns run padded to whole lane tiles in ``lane_block`` column
    blocks, and the output is sliced back to D.

    Head-major weights w: (heads, R, F) give every head its own edge
    weights over its dh = D // heads columns in one call:
    out[i, c] = sum_f w[c // dh, i, f]*mask[i,f]*h[nbr[i,f], c], bitwise
    the concatenation of per-head calls, with each row gathered once.
    """
    N, D = h.shape
    R, F = nbr.shape
    if block_n is None:
        block_n = auto_block_n(R)
    assert R % block_n == 0, (R, block_n)
    wm, dh = edge_weights(w, mask, h)
    hp = pad_lanes(h.astype(jnp.float32))
    Dp = hp.shape[1]
    block_d = lane_block(D, block_d)
    out = pl.pallas_call(
        functools.partial(_spmm_kernel, block_d=block_d, fanout=F,
                          block_n=block_n, dh=dh),
        grid=(R // block_n, Dp // block_d),
        in_specs=edge_specs(block_n, F, wm.shape[1]) + [
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((block_n, block_d), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, Dp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((F, block_n, block_d), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
        name="spmm",  # the op name traces and rooflines match on
    )(jnp.asarray(nbr, jnp.int32), wm, hp)
    return out[:, :D].astype(h.dtype)
