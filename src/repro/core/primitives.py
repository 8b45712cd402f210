"""DEAL's distributed GNN primitives (§3.4) in shard_map, plus the paper's
baselines (CAGNET-style GEMM, graph-exchange SPMM, SDDMM approach (i),
monolithic all-gather SPMM) for the benchmark comparisons.

Mesh geometry: ("data", "model") == DEAL's (P, M) grid.  All collectives
are explicit jax.lax calls so the communication schedule is exactly the
paper's: ring ppermute of requested feature rows (SPMM), two tiled
all-to-alls (GEMM), edge-scalar psum (SDDMM approach (ii)).

Multi-head attention splits heads over ``model`` (``head_layout``).
Where each shard owns whole heads (heads % M == 0) the per-head SDDMM,
the 1/sqrt(dh) scale and the masked edge softmax over the fanout run in
one shard_map (``dist_gat_attention``) with no score collective: the
shard dots its own lanes of q against the exchanged k rows.  Where a
head spans M / heads shards, the shards psum their partial edge scalars
(approach (ii)) before the softmax.  The attend is the ring SPMM with
head-major weights (r_loc, F, c): lane block j of a shard takes
``w[..., j]``; with plain (r_loc, F) weights it is the one-weight-per-
edge SPMM of GCN / SAGE.

These primitives are consumed through ``core.ops.DistExecutor`` (the
distributed backend of the pluggable executor layer); the ``make_*_p``
factories build jitted shard_map calls keyed only on static geometry
(P, fanout, variant) so one compiled function serves every layer — and
every row-subset refresh — with the same shapes.  The edge plans are
runtime arguments, so full-graph plans (``core.partition.build_plan``)
and frontier-subset plans (``build_subset_plan``) flow through the same
compiled collectives.

The single-host ``ref_*`` oracles are re-exported from ``kernels.ref``
— one canonical definition shared with the Pallas kernel tests, so the
two copies can never drift.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.gnn_models import edge_softmax
from repro.core.partition import LayerPlan
from repro.kernels.ref import F32_MATMUL
from repro.kernels.ref import gemm_ref as ref_gemm
from repro.kernels.ref import sddmm_ref as ref_sddmm
from repro.kernels.ref import spmm_ref as ref_spmm

__all__ = [
    "head_layout", "make_gemm", "make_gat_attention_p", "make_spmm",
    "make_spmm_p", "make_sddmm", "make_sddmm_p", "plan_device_arrays",
    "ref_gemm", "ref_spmm", "ref_sddmm",
]


def _named(fn, name: str):
    """``fn`` under ``name``, so its jitted module is ``jit_<name>`` in a
    profiler trace whatever wraps it."""
    def named(*args):
        return fn(*args)
    named.__name__ = named.__qualname__ = name
    return named


# ----------------------------------------------------------------------
# GEMM
# ----------------------------------------------------------------------

def _gemm_deal_local(H, W):
    """DEAL GEMM (Fig 7b): reshard rows over `model` with a tiled
    all-to-all, multiply with the replicated W, reshard back."""
    full = jax.lax.all_to_all(H, "model", split_axis=0, concat_axis=1,
                              tiled=True)              # (n/M, D)
    out = jnp.dot(full, W, preferred_element_type=jnp.float32,
                  precision=F32_MATMUL)
    out = out.astype(H.dtype)
    return jax.lax.all_to_all(out, "model", split_axis=1, concat_axis=0,
                              tiled=True)              # (n, D_out/M)


def _gemm_cagnet_local(H, W):
    """CAGNET-style allreduce GEMM (Fig 7a): full-width partials + column
    reduce-scatter.  (M-1)/M * n * D_out comm vs DEAL's 2(M-1)/M * n*D/M."""
    m = jax.lax.axis_index("model")
    d_loc = H.shape[1]
    w_slice = jax.lax.dynamic_slice_in_dim(W, m * d_loc, d_loc, 0)
    partial = jnp.dot(H, w_slice, preferred_element_type=jnp.float32,
                      precision=F32_MATMUL)
    out = jax.lax.psum_scatter(partial, "model", scatter_dimension=1,
                               tiled=True)
    return out.astype(H.dtype)


def _gemm_deal_ring_local(H, W, *, M: int):
    """DEAL GEMM with the explicit M-1-stage ring of Fig 7(b): at stage k
    each device ships the row-block addressed k hops away and ACCUMULATES
    the arriving chunk against the matching W row-slice, so stage k's
    matmul overlaps stage k+1's ppermute (the paper's pipelining)."""
    m = jax.lax.axis_index("model")
    n_loc, d_loc = H.shape
    blocks = H.reshape(M, n_loc // M, d_loc)

    def w_slice(j):
        return jax.lax.dynamic_slice_in_dim(W, j * d_loc, d_loc, 0)

    acc = jnp.dot(jnp.take(blocks, m, axis=0), w_slice(m),
                  preferred_element_type=jnp.float32, precision=F32_MATMUL)
    for k in range(1, M):
        send = jnp.take(blocks, (m + k) % M, axis=0)
        perm = [(i, (i + k) % M) for i in range(M)]
        recv = jax.lax.ppermute(send, "model", perm)
        acc = acc + jnp.dot(recv, w_slice((m - k) % M),
                            preferred_element_type=jnp.float32,
                            precision=F32_MATMUL)
    out = acc.astype(H.dtype)                       # (n/M, D_out)
    return jax.lax.all_to_all(out, "model", split_axis=1, concat_axis=0,
                              tiled=True)           # (n, D_out/M)


def make_gemm(mesh, variant: str = "deal"):
    if variant == "deal_ring":
        fn = functools.partial(_gemm_deal_ring_local,
                               M=mesh.shape["model"])
    else:
        fn = (_gemm_deal_local if variant == "deal"
              else _gemm_cagnet_local)
    return jax.jit(jax.shard_map(
        _named(fn, "dist_gemm"), mesh=mesh,
        in_specs=(P("data", "model"), P(None, None)),
        out_specs=P("data", "model")))


# ----------------------------------------------------------------------
# SPMM
# ----------------------------------------------------------------------

def _ring_bufs(H, send_local, P_: int):
    """Return the list of recv buffers for ring steps k = 1..P-1; buffer
    k-1 holds the rows this device requested from peer (p+k)%P.  All
    ppermutes are issued before any consumer runs — the monolithic
    (ungrouped) communication schedule."""
    bufs = []
    for k in range(1, P_):
        rows = jnp.take(H, send_local[k], axis=0)
        perm = [(i, (i - k) % P_) for i in range(P_)]
        bufs.append(jax.lax.ppermute(rows, "data", perm))
    return bufs


def _weigh(vals, w, dst, slot, mask):
    """Edge rows ``vals`` (E, d_loc) times their weights: one per edge
    for (r_loc, F) weights; for head-major (r_loc, F, c) weights, lane
    block j of the d_loc lanes times ``w[..., j]``."""
    if w.ndim == 2:
        return vals * (w[dst, slot] * mask).astype(jnp.float32)[:, None]
    c = w.shape[-1]
    we = (w[dst, slot] * mask[:, None]).astype(jnp.float32)     # (E, c)
    return (vals.reshape(vals.shape[0], c, -1)
            * we[:, :, None]).reshape(vals.shape)


def _accumulate(out, w, buf, dst, slot, pos, mask):
    vals = jnp.take(buf, pos, axis=0).astype(jnp.float32)
    return out.at[dst].add(_weigh(vals, w, dst, slot, mask))


def _spmm_deal_local(H, w, send_local, edge_dst, edge_slot, edge_pos,
                     edge_mask, *, P_: int, grouped: bool = True):
    """DEAL SPMM: ship only requested unique rows; grouped accumulation.

    H (u_loc, d_loc) source rows; w (r_loc, F) edge weights, or
    head-major (r_loc, F, c) ones (``_weigh``) — output rows follow w,
    so a frontier subset (r_loc < u_loc) runs through the same
    compiled collective as the full graph (r_loc == u_loc).  Plan arrays
    squeezed to this device: send_local (P, R), edge_* (P, E).
    """
    d_loc = H.shape[1]
    out = jnp.zeros((w.shape[0], d_loc), jnp.float32)
    # group 0: local tile first (Fig 12c — covers pipeline fill)
    out = _accumulate(out, w, H, edge_dst[0], edge_slot[0], edge_pos[0],
                      edge_mask[0])
    if grouped:
        for k in range(1, P_):
            rows = jnp.take(H, send_local[k], axis=0)
            perm = [(i, (i - k) % P_) for i in range(P_)]
            buf = jax.lax.ppermute(rows, "data", perm)
            out = _accumulate(out, w, buf, edge_dst[k], edge_slot[k],
                              edge_pos[k], edge_mask[k])
    else:
        # monolithic: all communication completes before any compute
        bufs = _ring_bufs(H, send_local, P_)
        for k in range(1, P_):
            out = _accumulate(out, w, bufs[k - 1], edge_dst[k],
                              edge_slot[k], edge_pos[k], edge_mask[k])
    return out.astype(H.dtype)


def _spmm_allgather_local(H, w, nbr, mask, *, P_: int):
    """Graph-partition-only baseline (Fig 3b): all-gather the FULL feature
    tile over `data` then gather locally — the memory blowup DEAL avoids."""
    full = jax.lax.all_gather(H, "data", axis=0, tiled=True)  # (N, d_loc)
    vals = jnp.take(full, nbr.reshape(-1), axis=0).astype(jnp.float32)
    vals = vals.reshape(nbr.shape + (H.shape[1],))
    out = (vals * (w * mask).astype(jnp.float32)[..., None]).sum(axis=1)
    return out.astype(H.dtype)


def _spmm_graph_exchange_local(H, w, mirror_src, edge_dst, edge_slot,
                               edge_mask, *, P_: int):
    """'Exchange G0' baseline (§3.4): the SOURCE owner gathers per-edge rows
    (duplicates included) and ships them to the destination — Z x more
    traffic than DEAL's unique-row exchange."""
    d_loc = H.shape[1]
    out = jnp.zeros((w.shape[0], d_loc), jnp.float32)
    # k=0: mirror_src == local row ids for the local group
    out = _accumulate(out, w, H, edge_dst[0], edge_slot[0], mirror_src[0],
                      edge_mask[0])
    for k in range(1, P_):
        contrib = jnp.take(H, mirror_src[k], axis=0)       # (E, d_loc) dup!
        perm = [(i, (i - k) % P_) for i in range(P_)]
        buf = jax.lax.ppermute(contrib, "data", perm)
        out = out.at[edge_dst[k]].add(_weigh(
            buf.astype(jnp.float32), w, edge_dst[k], edge_slot[k],
            edge_mask[k]))
    return out.astype(H.dtype)


def _squeeze0(x):
    return x[0]


def make_spmm_p(mesh, P_: int, variant: str = "deal",
                grouped: bool = True):
    """Jitted SPMM keyed on static geometry only (P, variant, grouped);
    the per-layer plan tensors are runtime arguments, so one compiled
    function serves every layer and every frontier-subset plan.

    The deal and graph-exchange variants also take head-major
    (N, F, c) weights, sharded over ``model`` on their last axis (c a
    multiple of M): a second program of the same name, picked by the
    weights' rank."""
    plan_spec = P("data", None, None)

    if variant == "allgather":
        def fn(H, w, nbr, mask):
            return _spmm_allgather_local(H, w, nbr[0], mask[0], P_=P_)
        return jax.jit(jax.shard_map(
            _named(fn, "dist_spmm"), mesh=mesh,
            in_specs=(P("data", "model"), P("data", None),
                      P("data", None, None), P("data", None, None)),
            out_specs=P("data", "model")))

    if variant == "graph_exchange":
        def fn(H, w, mirror_src, edge_dst, edge_slot, edge_mask):
            return _spmm_graph_exchange_local(
                H, w, mirror_src[0], edge_dst[0], edge_slot[0],
                edge_mask[0], P_=P_)
        n_plan = 4
    else:
        def fn(H, w, send_local, edge_dst, edge_slot, edge_pos, edge_mask):
            return _spmm_deal_local(
                H, w, send_local[0], edge_dst[0], edge_slot[0],
                edge_pos[0], edge_mask[0], P_=P_, grouped=grouped)
        n_plan = 5

    def program(w_spec):
        return jax.jit(jax.shard_map(
            _named(fn, "dist_spmm"), mesh=mesh,
            in_specs=(P("data", "model"), w_spec) + (plan_spec,) * n_plan,
            out_specs=P("data", "model")))

    per_edge = program(P("data", None))
    head_major = program(P("data", None, "model"))

    def spmm(H, w, *plan):
        return (head_major if w.ndim == 3 else per_edge)(H, w, *plan)
    return spmm


def make_spmm(mesh, lp: LayerPlan, variant: str = "deal",
              grouped: bool = True):
    return make_spmm_p(mesh, lp.P, variant, grouped)


# ----------------------------------------------------------------------
# SDDMM
# ----------------------------------------------------------------------

def _sddmm_deal_local(q, kf, send_local, edge_dst, edge_slot, edge_pos,
                      edge_mask, *, P_: int, fanout: int):
    """Approach (ii): partial dots over this device's D/M slice, then psum
    the edge SCALARS over `model` (exchange results, not features)."""
    n_loc = q.shape[0]
    attn = jnp.zeros((n_loc, fanout), jnp.float32)

    def acc(attn, buf, k):
        part = (jnp.take(q, edge_dst[k], axis=0).astype(jnp.float32)
                * jnp.take(buf, edge_pos[k], axis=0).astype(jnp.float32)
                ).sum(-1)
        part = part * edge_mask[k]
        return attn.at[edge_dst[k], edge_slot[k]].add(part)

    attn = acc(attn, kf, 0)
    for k in range(1, P_):
        rows = jnp.take(kf, send_local[k], axis=0)
        perm = [(i, (i - k) % P_) for i in range(P_)]
        buf = jax.lax.ppermute(rows, "data", perm)
        attn = acc(attn, buf, k)
    return jax.lax.psum(attn, "model")


def _sddmm_dup_local(q, kf, send_local, edge_dst, edge_slot, edge_pos,
                     edge_mask, *, P_: int, fanout: int):
    """Approach (i): all-gather the FULL feature columns over `model`
    (duplicate the computation), no result exchange."""
    qf = jax.lax.all_gather(q, "model", axis=1, tiled=True)   # (n_loc, D)
    kff = jax.lax.all_gather(kf, "model", axis=1, tiled=True)
    n_loc = q.shape[0]
    attn = jnp.zeros((n_loc, fanout), jnp.float32)

    def acc(attn, buf, k):
        part = (jnp.take(qf, edge_dst[k], axis=0).astype(jnp.float32)
                * jnp.take(buf, edge_pos[k], axis=0).astype(jnp.float32)
                ).sum(-1)
        return attn.at[edge_dst[k], edge_slot[k]].add(part * edge_mask[k])

    attn = acc(attn, kff, 0)
    for k in range(1, P_):
        rows = jnp.take(kff, send_local[k], axis=0)
        perm = [(i, (i - k) % P_) for i in range(P_)]
        buf = jax.lax.ppermute(rows, "data", perm)
        attn = acc(attn, buf, k)
    return attn


def make_sddmm_p(mesh, P_: int, fanout: int, variant: str = "deal"):
    """Jitted SDDMM keyed on static geometry only (P, fanout, variant) —
    see ``make_spmm_p``."""
    local = _sddmm_deal_local if variant == "deal" else _sddmm_dup_local
    plan_spec = P("data", None, None)

    def fn(q, kf, send_local, edge_dst, edge_slot, edge_pos, edge_mask):
        return local(q, kf, send_local[0], edge_dst[0], edge_slot[0],
                     edge_pos[0], edge_mask[0], P_=P_, fanout=fanout)
    # approach (i) duplicates the computation, so its output is replicated
    # over `model` by construction — not statically inferable (check_vma).
    return jax.jit(jax.shard_map(
        _named(fn, "dist_sddmm"), mesh=mesh,
        in_specs=(P("data", "model"), P("data", "model")) + (plan_spec,) * 5,
        out_specs=P("data", None), check_vma=(variant == "deal")))


def make_sddmm(mesh, lp: LayerPlan, variant: str = "deal"):
    return make_sddmm_p(mesh, lp.P, lp.fanout, variant)


# ----------------------------------------------------------------------
# multi-head attention: per-head SDDMM + edge softmax
# ----------------------------------------------------------------------

def head_layout(heads: int, M: int) -> int:
    """Score columns one model shard computes: heads / M whole heads, or
    1 (a part of one head) where a head spans M / heads shards.  Any
    other (heads, M) pair cannot be split."""
    if heads % M == 0:
        return heads // M
    if M % heads == 0:
        return 1
    raise ValueError(f"{heads} heads cannot be split over a model axis "
                     f"of {M}: one must divide the other")


def _head_scores_local(q, kf, send_local, edge_dst, edge_slot, edge_pos,
                       edge_mask, *, P_: int, fanout: int, heads: int,
                       M: int):
    """Per-head dot scores (n_loc, F, c) from this shard's lanes of q
    and of the exchanged k rows.  With whole heads here (c = heads / M)
    no collective; a head spanning M / heads shards psums its partial
    edge scalars over ``model`` (approach (ii)), c = heads."""
    n_loc, d_loc = q.shape
    c = head_layout(heads, M)
    attn = jnp.zeros((n_loc, fanout, c), jnp.float32)

    def acc(attn, buf, k):
        qe = jnp.take(q, edge_dst[k], axis=0).astype(jnp.float32)
        ke = jnp.take(buf, edge_pos[k], axis=0).astype(jnp.float32)
        part = (qe * ke).reshape(-1, c, d_loc // c).sum(-1)
        part = part * edge_mask[k][:, None]
        return attn.at[edge_dst[k], edge_slot[k]].add(part)

    attn = acc(attn, kf, 0)
    for k in range(1, P_):
        rows = jnp.take(kf, send_local[k], axis=0)
        perm = [(i, (i - k) % P_) for i in range(P_)]
        buf = jax.lax.ppermute(rows, "data", perm)
        attn = acc(attn, buf, k)
    if heads >= M:
        return attn
    head = jax.lax.axis_index("model") // (M // heads)
    full = jnp.zeros((n_loc, fanout, heads), jnp.float32)
    full = jax.lax.dynamic_update_slice_in_dim(full, attn, head, axis=2)
    return jax.lax.psum(full, "model")


def _gat_attention_local(q, kf, mask, *plan, P_: int, fanout: int,
                         heads: int, M: int, softmax: bool):
    dh = q.shape[1] * M // heads
    s = _head_scores_local(q, kf, *plan, P_=P_, fanout=fanout,
                           heads=heads, M=M) / jnp.sqrt(jnp.float32(dh))
    return edge_softmax(s, mask > 0) if softmax else s


def make_gat_attention_p(mesh, P_: int, fanout: int, heads: int,
                         softmax: bool = True):
    """Jitted per-head attention keyed on static geometry (P, fanout,
    heads): ``fn(q, kf, mask_f, *deal_plan_args)`` -> (N, F, heads),
    the scaled scores, normalized over each row's masked-in slots when
    ``softmax``.  Sharded over ``model`` on the heads axis where each
    shard owns whole heads, replicated over it where a head spans
    shards.  Named ``dist_gat_attention`` (``dist_sddmm`` unfused)."""
    M = mesh.shape["model"]
    head_layout(heads, M)               # raises on a pair it cannot split
    plan_spec = P("data", None, None)

    def fn(q, kf, mask, *plan):
        return _gat_attention_local(q, kf, mask, *(a[0] for a in plan),
                                    P_=P_, fanout=fanout, heads=heads,
                                    M=M, softmax=softmax)
    name = "dist_gat_attention" if softmax else "dist_sddmm"
    return jax.jit(jax.shard_map(
        _named(fn, name), mesh=mesh,
        in_specs=(P("data", "model"), P("data", "model"),
                  P("data", None)) + (plan_spec,) * 5,
        out_specs=P("data", None, "model" if heads >= M else None)))


# ----------------------------------------------------------------------
# single-host references: re-exported from kernels.ref (see module
# docstring) — ref_gemm / ref_spmm / ref_sddmm are bound in the imports.
# ----------------------------------------------------------------------

def plan_device_arrays(lp: LayerPlan, sharding=None) -> Dict[str, Any]:
    """The per-layer plan tensors shipped to devices (leading dim = P,
    sharded over `data`: placed so where ``sharding`` is given, else on
    the default device and resharded by each call)."""
    put = (jnp.asarray if sharding is None
           else functools.partial(jax.device_put, device=sharding))
    return {name: put(getattr(lp, name))
            for name in ("send_local", "edge_dst", "edge_slot", "edge_pos",
                         "edge_mask", "mirror_src")}
