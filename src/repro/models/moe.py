"""Top-k MoE with sort-based capacity dispatch (pjit/GSPMD-friendly).

Dispatch is a static-shape argsort bucketing: tokens are sorted by expert id,
assigned a position within their expert bucket, and scattered into an
(E, C, D) buffer (capacity C; overflow tokens are dropped, standard for
capacity-based MoE).  The expert FFN is a single batched einsum over E so the
expert axis shards cleanly over the `model` mesh axis.  This is the SPMM-like
"route only what's needed" pattern of the paper applied to expert routing.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.sharding.context import constrain


def _capacity(n_tokens: int, n_experts: int, top_k: int, factor: float) -> int:
    c = int(n_tokens * top_k / n_experts * factor) + 1
    return max(c, 4)


def moe_ffn(buf: jax.Array, w_gate, w_up, w_down) -> jax.Array:
    """buf: (E, C, D); expert weights (E, D, F) / (E, F, D)."""
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate))
    u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    return jnp.einsum("ecf,efd->ecd", g * u, w_down)


def _dispatch_compute(flat, logits, w_gate, w_up, w_down, *, n_experts,
                      top_k, capacity, expert_offset=0):
    """Sort-based capacity dispatch over `n_experts` LOCAL experts.

    flat: (T, D); logits: (T, E_total) f32.  Tokens routed to experts
    outside [expert_offset, expert_offset + n_experts) are masked out.
    Returns (out (T, D), gate (T, K), expert (T, K)).
    """
    T, D = flat.shape
    E, K, C = n_experts, top_k, capacity
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert = jax.lax.top_k(probs, K)                   # (T, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    eflat = expert.reshape(T * K) - expert_offset
    local = (eflat >= 0) & (eflat < E)
    eflat = jnp.where(local, eflat, E)                       # E == "drop"
    gflat = gate.reshape(T * K)
    tok = jnp.arange(T * K) // K
    order = jnp.argsort(eflat)
    es, ts, gs = eflat[order], tok[order], gflat[order]
    starts = jnp.searchsorted(es, jnp.arange(E))
    pos = jnp.arange(T * K) - starts[jnp.minimum(es, E - 1)]
    keep = (pos < C) & (es < E)
    slot = jnp.where(keep, es * C + pos, E * C)

    buf = jnp.zeros((E * C + 1, D), flat.dtype)
    buf = buf.at[slot].set(flat[ts] * keep[:, None].astype(flat.dtype))
    out_buf = moe_ffn(buf[:-1].reshape(E, C, D), w_gate, w_up, w_down)
    out_flat = jnp.concatenate(
        [out_buf.reshape(E * C, D), jnp.zeros((1, D), flat.dtype)])
    gathered = out_flat[slot] * (gs * keep)[:, None].astype(flat.dtype)
    out = jnp.zeros((T, D), flat.dtype).at[ts].add(gathered)
    return out, probs, expert


def moe_block(x: jax.Array, p, cfg) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D).  Returns (out, aux_loss).

    With REPRO_TUNING=moe_ep and an active sharding context, dispatch runs
    expert-parallel inside shard_map (H2); otherwise the pjit/GSPMD global
    scatter path (baseline).
    """
    from repro import tuning
    from repro.sharding.context import current_mesh
    mesh = current_mesh()
    if tuning.on("moe_ep") and mesh is not None:
        return _moe_block_ep(x, p, cfg, mesh)
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    E, K = m.n_experts, m.top_k
    C = _capacity(T, E, K, m.capacity_factor)
    flat = x.reshape(T, D)

    logits = jnp.einsum("td,de->te", flat.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert = jax.lax.top_k(probs, K)                   # (T, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # ---- load-balance aux loss (Switch-style) ----
    me = probs.mean(axis=0)                                  # (E,)
    one_hot = jax.nn.one_hot(expert[:, 0], E, dtype=jnp.float32)
    ce = one_hot.mean(axis=0)
    aux = E * jnp.sum(me * ce)

    # ---- sort-based dispatch ----
    eflat = expert.reshape(T * K)
    gflat = gate.reshape(T * K)
    tok = jnp.arange(T * K) // K
    order = jnp.argsort(eflat)
    es, ts, gs = eflat[order], tok[order], gflat[order]
    starts = jnp.searchsorted(es, jnp.arange(E))             # (E,)
    pos = jnp.arange(T * K) - starts[es]
    keep = pos < C
    slot = jnp.where(keep, es * C + pos, E * C)              # drop -> scratch

    buf = jnp.zeros((E * C + 1, D), x.dtype)
    buf = buf.at[slot].set(flat[ts] * keep[:, None].astype(x.dtype))
    buf = constrain(buf[:-1].reshape(E, C, D), "tp", "dp")
    out_buf = constrain(moe_ffn(buf, p["w_gate"], p["w_up"], p["w_down"]),
                        "tp", "dp")
    out_flat = jnp.concatenate(
        [out_buf.reshape(E * C, D), jnp.zeros((1, D), x.dtype)])
    gathered = out_flat[slot] * (gs * keep)[:, None].astype(x.dtype)
    out = constrain(jnp.zeros((T, D), x.dtype).at[ts].add(gathered), "dp")

    # ---- shared experts (always-on dense path) ----
    if m.n_shared_experts:
        g = jax.nn.silu(jnp.einsum("td,df->tf", flat, p["shared_w_gate"]))
        u = jnp.einsum("td,df->tf", flat, p["shared_w_up"])
        out = out + jnp.einsum("tf,fd->td", g * u, p["shared_w_down"])

    return out.reshape(B, S, D), aux


def _moe_block_ep(x, p, cfg, mesh):
    """H2: expert-parallel MoE in shard_map.

    Tokens are sharded over the dp axes and REPLICATED over `model`; each
    model-chip dispatches every local token to ITS E/M experts only and the
    per-chip partial outputs (zero where not routed here) psum over
    `model` — one activation-sized collective per MoE layer instead of the
    replicated global scatter.  This is DEAL's "only the owners compute,
    exchange the small results" applied to expert routing.
    """
    from jax.sharding import PartitionSpec as P
    from repro.sharding.specs import logical_axes, shard_if_divisible

    m = cfg.moe
    B, S, D = x.shape
    E, K = m.n_experts, m.top_k
    ax = logical_axes(mesh)
    dp, tp = ax["dp"], ax["tp"]
    M = mesh.shape["model"]
    assert E % M == 0, (E, M)
    E_loc = E // M
    import math
    b_ax = shard_if_divisible(mesh, B, dp)
    n_dp = 1 if b_ax is None else math.prod(mesh.shape[a] for a in dp)
    T_loc = (B // n_dp) * S
    C = _capacity(T_loc, E, K, m.capacity_factor)

    def local(x, router, w_gate, w_up, w_down):
        # x: (B_loc, S, D); experts: (E_loc, D, F)
        x = x.reshape(-1, D)
        logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                            router.astype(jnp.float32))
        m_idx = jax.lax.axis_index("model")
        out, probs, expert = _dispatch_compute(
            x, logits, w_gate, w_up, w_down, n_experts=E_loc, top_k=K,
            capacity=C, expert_offset=m_idx * E_loc)
        out = jax.lax.psum(out, "model")
        # aux loss (identical on every model chip before psum-mean)
        me = probs.mean(axis=0)
        ce = jax.nn.one_hot(expert[:, 0], E, dtype=jnp.float32).mean(axis=0)
        aux = E * jnp.sum(me * ce)
        if b_ax is not None:
            aux = jax.lax.pmean(aux, dp)
        return out, aux

    in_specs = (P(b_ax, None, None), P(None, None),
                P("model", None, None), P("model", None, None),
                P("model", None, None))
    out, aux = jax.shard_map(
        local, mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(b_ax, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    out = out.reshape(B, S, D)

    if m.n_shared_experts:
        flat = x.reshape(B * S, D)
        g = jax.nn.silu(jnp.einsum("td,df->tf", flat, p["shared_w_gate"]))
        u = jnp.einsum("td,df->tf", flat, p["shared_w_up"])
        out = out + jnp.einsum("tf,fd->td", g * u,
                               p["shared_w_down"]).reshape(B, S, D)
    return out, aux


def init_moe_params(rng, cfg, dtype):
    m = cfg.moe
    D, E, F = cfg.d_model, m.n_experts, m.d_ff_expert
    k = jax.random.split(rng, 7)
    init = jax.nn.initializers.normal(0.02)
    p = {
        "router": init(k[0], (D, E), jnp.float32),
        "w_gate": init(k[1], (E, D, F), dtype),
        "w_up": init(k[2], (E, D, F), dtype),
        "w_down": init(k[3], (E, F, D), dtype),
    }
    if m.n_shared_experts:
        Fs = F * m.n_shared_experts
        p["shared_w_gate"] = init(k[4], (D, Fs), dtype)
        p["shared_w_up"] = init(k[5], (D, Fs), dtype)
        p["shared_w_down"] = init(k[6], (Fs, D), dtype)
    return p
