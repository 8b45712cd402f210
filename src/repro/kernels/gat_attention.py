"""Pallas TPU kernel: fused SDDMM + masked edge softmax (GAT attention).

The unfused GAT spec runs one SDDMM kernel call PER HEAD (a Python loop
round-tripping each (N, F) score slice through HBM), stacks the slices,
scales, and then runs a separate masked-softmax op.  This kernel
produces the normalized attention alpha (N, F, heads) in ONE pass per
node block: gather each edge's k row once, compute ALL heads' scaled
dot scores in VMEM, and normalize over the fanout axis before anything
is written back — the score tensor never exists in HBM.

q/mask tiles are staged per node block in VMEM and the ids in SMEM; k
stays in HBM and each edge's row is DMA'd into a (F, bn, D) VMEM scratch
(``spmm.gather_rows``), with q and k zero-padded to whole lane tiles.
A head's dot is a lane reduction of q * k_f over that head's lanes (the
others masked to 0.0, which adds exactly).
The kernel writes head-major (heads, N, F) tiles; the wrapper transposes
to (N, F, heads).  The math is op-for-op ``ref.gat_attention_ref`` (same
f32 dots, same /sqrt(dh), same -1e30 masked fill, same softmax), so
fused and unfused paths verify against the same oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spmm import (auto_block_n, edge_specs, gather_rows,
                                pad_lanes)


def _gat_attention_kernel(nbr_ref, mask_ref, q_ref, k_hbm, o_ref, rows_ref,
                          sem, *, fanout: int, block_n: int, heads: int,
                          dh: int):
    gather_rows(k_hbm, rows_ref, sem, lambda r, f: nbr_ref[r, f],
                block_n=block_n, fanout=fanout, width=k_hbm.shape[1])
    q = q_ref[...]
    # pad lanes get head id >= heads, so they join no head's dot
    head_of = jax.lax.broadcasted_iota(jnp.int32, q.shape, 1) // dh
    m = mask_ref[...] > 0                                       # (bn, F)
    slot = jax.lax.broadcasted_iota(jnp.int32, m.shape, 1)
    s = [jnp.zeros(m.shape, jnp.float32) for _ in range(heads)]
    for f in range(fanout):
        prod = q * rows_ref[f]                                  # (bn, D)
        for h in range(heads):
            dot = jnp.sum(jnp.where(head_of == h, prod, 0.0), axis=1,
                          keepdims=True)                        # (bn, 1)
            s[h] = jnp.where(slot == f, dot, s[h])
    for h in range(heads):
        x = jnp.where(m, s[h] / jnp.sqrt(jnp.float32(dh)), -1e30)
        e = jnp.exp(x - jnp.max(x, axis=1, keepdims=True))
        p = e / jnp.sum(e, axis=1, keepdims=True)
        o_ref[h] = jnp.where(m, p, 0.0)


@functools.partial(jax.jit, static_argnames=("heads", "block_n",
                                             "interpret"))
def gat_attention(q, k, nbr, mask, *, heads: int = 1, block_n: int = None,
                  interpret: bool):
    """alpha[i,f,h] = edge_softmax_f(<q_h[i], k_h[nbr[i,f]]>/sqrt(dh)).

    q: (N, D) head-major; k: (U, D) source table (U and N decouple for
    row-subset execution); nbr, mask: (N, F).  Returns the NORMALIZED
    per-head attention (N, F, heads) f32 — scores and softmax fused, no
    HBM round-trip of the score tensor.  N % block_n == 0,
    D % heads == 0.
    """
    N, D = q.shape
    F = nbr.shape[1]
    assert D % heads == 0, (D, heads)
    if block_n is None:
        block_n = auto_block_n(N)
    assert N % block_n == 0, (N, block_n)
    mask_f = mask.astype(jnp.float32)
    q = pad_lanes(q.astype(jnp.float32))
    k = pad_lanes(k.astype(jnp.float32))
    Dp = q.shape[1]
    alpha = pl.pallas_call(
        functools.partial(_gat_attention_kernel, fanout=F, block_n=block_n,
                          heads=heads, dh=D // heads),
        grid=(N // block_n,),
        in_specs=edge_specs(block_n, F) + [
            pl.BlockSpec((block_n, Dp), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((heads, block_n, F), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((heads, N, F), jnp.float32),
        scratch_shapes=[pltpu.VMEM((F, block_n, Dp), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
    )(jnp.asarray(nbr, jnp.int32), mask_f, q, k)
    return alpha.transpose(1, 2, 0)
