"""Attention variants: GQA flash (chunked online-softmax), decode, MLA.

All math in f32 accumulators, inputs/outputs in the activation dtype.
The chunked prefill path is the pure-JAX twin of kernels/flash_attention.py
(the Pallas TPU kernel); tests assert they agree.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.sharding.context import constrain

_NEG_INF = -1e30


def _mask(q_pos, kv_pos, *, causal: bool, window: Optional[int]):
    """(bq, bk) boolean mask from absolute positions."""
    m = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), dtype=bool)
    if causal:
        m &= kv_pos[None, :] <= q_pos[:, None]
    if window is not None:
        m &= (q_pos[:, None] - kv_pos[None, :]) < window
    return m


def simple_attention(q, k, v, *, q_offset=0, causal=True,
                     window: Optional[int] = None,
                     kv_valid_len: Optional[jax.Array] = None,
                     scale: Optional[float] = None):
    """Reference unchunked GQA attention.  q:(B,Sq,H,hd) k,v:(B,Skv,K,hd)."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qf = q.reshape(B, Sq, K, G, hd).astype(jnp.float32) * scale
    s = jnp.einsum("bqkgd,bskd->bkgqs", qf, k.astype(jnp.float32))
    q_pos = q_offset + jnp.arange(Sq)
    kv_pos = jnp.arange(k.shape[1])
    m = _mask(q_pos, kv_pos, causal=causal, window=window)
    if kv_valid_len is not None:
        m &= (kv_pos < kv_valid_len)[None, :]
    s = jnp.where(m[None, None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


class _Carry(NamedTuple):
    m: jax.Array
    l: jax.Array
    acc: jax.Array


def flash_attention_jnp(q, k, v, *, q_offset=0, causal=True,
                        window: Optional[int] = None,
                        q_block: int = 512, kv_block: int = 1024,
                        scale: Optional[float] = None):
    """Double-chunked online-softmax attention (memory O(block^2)).

    q: (B, Sq, H, hd); k, v: (B, Skv, K, hd) with H % K == 0.
    """
    B, Sq, H, hd = q.shape
    Skv, K = k.shape[1], k.shape[2]
    vd = v.shape[-1]               # may differ from hd (MLA)
    G = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Skv)
    # pad ragged sequence lengths to block multiples (whisper's 1500 frames
    # etc.); padded kv is masked out, padded q rows are dropped at the end.
    Sq_orig, Skv_orig = Sq, Skv
    q_pad = (-Sq) % q_block
    kv_pad = (-Skv) % kv_block
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0)))
        Sq += q_pad
    if kv_pad:
        k = jnp.pad(k, ((0, 0), (0, kv_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, kv_pad), (0, 0), (0, 0)))
        Skv += kv_pad
    nq, nk = Sq // q_block, Skv // kv_block

    # (nq, B, bq, K, G, hd) / (nk, B, bk, K, hd)
    qb = q.reshape(B, nq, q_block, K, G, hd).transpose(1, 0, 2, 3, 4, 5)
    kb = k.reshape(B, nk, kv_block, K, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nk, kv_block, K, vd).transpose(1, 0, 2, 3, 4)
    qb = constrain(qb, None, "dp")
    kb = constrain(kb, None, "dp")
    vb = constrain(vb, None, "dp")

    def q_step(_, qi_and_idx):
        qi, iq = qi_and_idx
        qf = qi.astype(jnp.float32) * scale
        q_pos = q_offset + iq * q_block + jnp.arange(q_block)

        @jax.checkpoint
        def kv_step(carry: _Carry, ki_vi_idx):
            # checkpointed: the bwd recomputes s/p per block (flash bwd)
            # instead of saving (bq, bk) score tensors per kv iteration.
            ki, vi, ik = ki_vi_idx
            kv_pos = ik * kv_block + jnp.arange(kv_block)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qf, ki.astype(jnp.float32))
            s = constrain(s, "dp")
            msk = _mask(q_pos, kv_pos, causal=causal, window=window)
            msk &= (kv_pos < Skv_orig)[None, :]
            s = jnp.where(msk[None, None, None], s, _NEG_INF)
            m_new = jnp.maximum(carry.m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(carry.m - m_new)
            l_new = carry.l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p, vi.astype(jnp.float32))
            acc_new = carry.acc * corr[..., None] + pv
            return _Carry(m_new, l_new, acc_new), None

        init = _Carry(
            m=constrain(jnp.full((B, K, G, q_block), _NEG_INF, jnp.float32),
                        "dp"),
            l=constrain(jnp.zeros((B, K, G, q_block), jnp.float32), "dp"),
            acc=constrain(jnp.zeros((B, K, G, q_block, vd), jnp.float32),
                          "dp"),
        )
        carry, _ = jax.lax.scan(
            kv_step, init, (kb, vb, jnp.arange(nk)))
        out = carry.acc / jnp.maximum(carry.l, 1e-30)[..., None]
        # (B, K, G, bq, hd) -> (B, bq, K, G, hd)
        return None, out.transpose(0, 3, 1, 2, 4)

    _, blocks = jax.lax.scan(q_step, None, (qb, jnp.arange(nq)))
    # (nq, B, bq, K, G, vd) -> (B, Sq, H, vd)
    out = blocks.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, vd)
    return out[:, :Sq_orig].astype(q.dtype)


def decode_attention(q, k_cache, v_cache, *, cache_len,
                     window: Optional[int] = None,
                     scale: Optional[float] = None):
    """One-token GQA decode against a (B, S, K, hd) cache.

    ``cache_len``: number of valid cache entries per sequence — scalar or
    (B,) vector (continuous batching: slots may be at different lengths).
    The new token sits at cache_len - 1.  O(S) compute per token.
    """
    from repro import tuning

    B, Sq, H, hd = q.shape
    assert Sq == 1
    S, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qf = q.reshape(B, K, G, hd).astype(jnp.float32) * scale
    if tuning.on("gqa_cache_seq"):
        # cache S is tp-sharded: replicate the (tiny) q over `model` so the
        # score einsum stays shard-local instead of gathering the cache
        qf = constrain(qf, "dp", None, None, None)
        s = jnp.einsum("bkgd,bskd->bkgs", qf, k_cache.astype(jnp.float32))
        s = constrain(s, "dp", None, None, "tp")
    else:
        s = jnp.einsum("bkgd,bskd->bkgs", qf, k_cache.astype(jnp.float32))
        s = constrain(s, "dp")
    kv_pos = jnp.arange(S)
    clen = jnp.broadcast_to(jnp.asarray(cache_len), (B,))[:, None]  # (B,1)
    msk = kv_pos[None, :] < clen
    if window is not None:
        msk &= (clen - 1 - kv_pos[None, :]) < window
    s = jnp.where(msk[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def cp_decode_attention(q, k_cache, v_cache, *, cache_len, mesh,
                        window: Optional[int] = None,
                        scale: Optional[float] = None):
    """H3: sequence-parallel decode attention (long_500k path).

    The KV cache is sharded over `data` along the sequence; instead of
    letting GSPMD all-gather the whole cache per layer, each shard computes
    its local (m, l, acc) online-softmax partials and ONLY those are
    psum/pmax'd — the paper's "exchange the small partial results, never
    the big tensor" (§3.4 SPMM / SDDMM-(ii)) applied to attention.
    Collective payload: O(B*H*hd) per layer vs O(S*K*hd).
    """
    from jax.sharding import PartitionSpec as P

    B, Sq, H, hd = q.shape
    assert Sq == 1
    S, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    n_shards = mesh.shape["data"]
    S_loc = S // n_shards
    scale_ = scale if scale is not None else 1.0 / math.sqrt(hd)

    def local(q, kc, vc, clen):
        i = jax.lax.axis_index("data")
        kv_pos = i * S_loc + jnp.arange(S_loc)
        qf = q.reshape(B, K, G, hd).astype(jnp.float32) * scale_
        s = jnp.einsum("bkgd,bskd->bkgs", qf, kc.astype(jnp.float32))
        cl = jnp.broadcast_to(clen, (B,))[:, None]
        msk = kv_pos[None, :] < cl
        if window is not None:
            msk &= (cl - 1 - kv_pos[None, :]) < window
        s = jnp.where(msk[:, None, None, :], s, _NEG_INF)
        m_loc = s.max(axis=-1)                          # (B,K,G)
        m_glob = jax.lax.pmax(m_loc, "data")
        p = jnp.exp(s - m_glob[..., None])
        l = jax.lax.psum(p.sum(axis=-1), "data")
        acc = jnp.einsum("bkgs,bskd->bkgd", p, vc.astype(jnp.float32))
        acc = jax.lax.psum(acc, "data")
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.reshape(B, 1, H, hd).astype(q.dtype)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(None, "data", None, None),
                  P(None, "data", None, None), P()),
        out_specs=P(), check_vma=False,
    )(q, k_cache, v_cache,
      jnp.broadcast_to(jnp.asarray(cache_len), (B,)))


# ----------------------------------------------------------------------
# MLA (DeepSeek-V2) — latent-space attention with absorbed decode.
# ----------------------------------------------------------------------

def mla_prefill(x, p, cfg, positions):
    """Multi-head latent attention, training/prefill path.

    p: dict with wq_a (D,qr), q_norm (qr,), wq_b (qr,H*(nope+rope)),
       wkv_a (D,kvr+rope), kv_norm (kvr,), wkv_b (kvr,H*(nope+v)),
       wo (H*v, D).
    Returns (out, c_kv, k_rope) so the caches can be kept for decode.
    """
    from repro.models.layers import rms_norm, rope as apply_rope

    a = cfg.mla
    B, S, D = x.shape
    H = cfg.n_heads
    nd, rd, vd = a.nope_head_dim, a.rope_head_dim, a.v_head_dim

    q_lat = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wq_a"]), p["q_norm"])
    q = jnp.einsum("bsr,rh->bsh", q_lat, p["wq_b"]).reshape(B, S, H, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    c_kv = rms_norm(kv_a[..., :a.kv_lora_rank], p["kv_norm"])
    k_rope = apply_rope(kv_a[..., None, a.kv_lora_rank:], positions,
                        cfg.rope_theta)  # (B,S,1,rd) shared across heads
    kv = jnp.einsum("bsr,rh->bsh", c_kv, p["wkv_b"]).reshape(B, S, H, nd + vd)
    k_nope, v = kv[..., :nd], kv[..., nd:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, rd))],
                        axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    scale = 1.0 / math.sqrt(nd + rd)
    o = flash_attention_jnp(q_full, k, v, causal=True, scale=scale)
    out = jnp.einsum("bshv,hvd->bsd", o.reshape(B, S, H, vd),
                     p["wo"].reshape(H, vd, D))
    return out, c_kv, k_rope[..., 0, :]


def mla_decode(x, p, cfg, c_kv_cache, k_rope_cache, cache_len, position):
    """Absorbed MLA decode: attend in the kv_lora latent space.

    c_kv_cache: (B, S, kvr) — already includes the current token's entry.
    """
    from repro.models.layers import rms_norm, rope as apply_rope

    a = cfg.mla
    B, Sq, D = x.shape
    assert Sq == 1
    H = cfg.n_heads
    nd, rd, vd = a.nope_head_dim, a.rope_head_dim, a.v_head_dim
    kvr = a.kv_lora_rank

    q_lat = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wq_a"]), p["q_norm"])
    q = jnp.einsum("bsr,rh->bsh", q_lat, p["wq_b"]).reshape(B, 1, H, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    pos_bs = jnp.broadcast_to(jnp.asarray(position), (B,))[:, None]  # (B,1)
    q_rope = apply_rope(q_rope, pos_bs, cfg.rope_theta)

    wkv_b = p["wkv_b"].reshape(kvr, H, nd + vd)
    w_uk, w_uv = wkv_b[..., :nd], wkv_b[..., nd:]
    # absorb W_uk into q: (B,1,H,kvr)
    q_abs = jnp.einsum("bqhn,rhn->bqhr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    scale = 1.0 / math.sqrt(nd + rd)
    s = jnp.einsum("bqhr,bsr->bhqs", q_abs,
                   c_kv_cache.astype(jnp.float32)) * scale
    s += jnp.einsum("bqhr,bsr->bhqs", q_rope.astype(jnp.float32) * scale,
                    k_rope_cache.astype(jnp.float32))
    kv_pos = jnp.arange(c_kv_cache.shape[1])
    clen = jnp.broadcast_to(jnp.asarray(cache_len), (B,))[:, None]
    s = jnp.where((kv_pos[None, :] < clen)[:, None, None, :], s, _NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhqs,bsr->bqhr", prob, c_kv_cache.astype(jnp.float32))
    o = jnp.einsum("bqhr,rhv->bqhv", o_lat, w_uv.astype(jnp.float32))
    out = jnp.einsum("bqhv,hvd->bqd", o.astype(x.dtype),
                     p["wo"].reshape(H, vd, D))
    return out


def mla_new_cache_entries(x, p, cfg, position):
    """Compute the (c_kv, k_rope) entries for one new token.

    ``position``: scalar or (B,) per-sequence positions.
    """
    from repro.models.layers import rms_norm, rope as apply_rope
    a = cfg.mla
    B = x.shape[0]
    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    c_kv = rms_norm(kv_a[..., :a.kv_lora_rank], p["kv_norm"])
    pos_bs = jnp.broadcast_to(jnp.asarray(position), (B,))[:, None]
    k_rope = apply_rope(kv_a[..., None, a.kv_lora_rank:], pos_bs,
                        cfg.rope_theta)[..., 0, :]
    return c_kv, k_rope
