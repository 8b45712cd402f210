"""Pure-jnp reference primitives — the ONE canonical definition.

These serve double duty: they are the allclose targets for the Pallas
kernels AND the math behind ``core.ops.RefExecutor`` (the single-host
oracle engine).  ``core.primitives`` re-exports them under the ``ref_*``
names, so the oracle cannot drift between the kernel tests and the
inference engines.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# f32 operands stay f32 on the MXU.  The TPU's default matmul precision
# rounds them to bf16 (about three significant digits), which would make
# every f32 model a bf16 one on the chip; the CPU ignores the setting.
F32_MATMUL = jax.lax.Precision.HIGHEST


def gemm_ref(h, w):
    """out = h @ w, accumulated in f32, cast back to h.dtype."""
    return jnp.dot(h, w, preferred_element_type=jnp.float32,
                   precision=F32_MATMUL).astype(h.dtype)


def spmm_ref(h, w, nbr, mask):
    """out[i] = sum_f w[i,f] * mask[i,f] * h[nbr[i,f]].  h:(N,D) nbr:(N,F)."""
    vals = jnp.take(h, nbr.reshape(-1), axis=0).astype(jnp.float32)
    vals = vals.reshape(nbr.shape + (h.shape[-1],))
    coef = (w * mask).astype(jnp.float32)[..., None]
    return (vals * coef).sum(axis=1).astype(h.dtype)


def sddmm_ref(q, k, nbr, mask):
    """e[i,f] = <q[i], k[nbr[i,f]]> * mask[i,f].  q,k:(N,D)."""
    vals = jnp.take(k, nbr.reshape(-1), axis=0).reshape(
        nbr.shape + (k.shape[-1],)).astype(jnp.float32)
    out = (q[:, None, :].astype(jnp.float32) * vals).sum(-1)
    return (out * mask).astype(jnp.float32)


def gather_spmm_ref(h, table, w, nbr, mask):
    """out[i] = sum_f w[i,f] * mask[i,f] * h[table[nbr[i,f]]].

    The fused-gather SPMM oracle: ``nbr`` carries UNTRANSLATED ids (global
    node ids, loader-order ids, ...) and ``table`` maps them onto rows of
    ``h`` — the indirection the Deal §3.5 fusion pushes into layer-1's
    gather instead of materializing ``h[table]``.  Resolving the ids and
    calling ``spmm_ref`` is bitwise-identical to gathering from a
    materialized reorder, because the per-row reductions see the same
    values in the same order.  Masked slots may map anywhere in-range:
    their coefficient is exactly 0.0 and adding 0.0 is exact.
    """
    idx = jnp.take(jnp.asarray(table), nbr.reshape(-1)).reshape(nbr.shape)
    return spmm_ref(h, w, idx, mask)


def gat_attention_ref(q, k, nbr, mask, heads: int):
    """Fused GAT edge attention oracle: per-head scaled dot scores +
    masked edge softmax in one pass — alpha (N, F, heads) f32.

    Matches ``gnn_models.gat_head_scores`` -> ``masked_softmax``
    op-for-op (same f32 dot, same /sqrt(dh), same -1e30 fill, same
    softmax), so the fused Pallas kernel and the unfused two-op spec
    path verify against the same math.
    """
    N, D = q.shape
    dh = D // heads
    qh = q.reshape(N, heads, dh).astype(jnp.float32)
    kh = k.reshape(-1, heads, dh).astype(jnp.float32)
    kn = jnp.take(kh, nbr.reshape(-1), axis=0).reshape(
        nbr.shape + (heads, dh))
    s = jnp.einsum("nhd,nfhd->nfh", qh, kn, precision=F32_MATMUL) / jnp.sqrt(
        jnp.float32(dh))
    m = mask[:, :, None]
    p = jax.nn.softmax(jnp.where(m, s, -1e30), axis=1)
    return p * m


def flash_attention_ref(q, k, v, *, causal=True):
    """q:(BH,Sq,hd) k,v:(BH,Skv,hd) — plain softmax attention, f32."""
    BH, Sq, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("bqd,bsd->bqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        m = jnp.arange(k.shape[1])[None, :] <= jnp.arange(Sq)[:, None]
        s = jnp.where(m[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqs,bsd->bqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
