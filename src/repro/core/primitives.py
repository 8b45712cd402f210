"""DEAL's distributed GNN primitives (§3.4) in shard_map, plus the paper's
baselines (CAGNET-style GEMM, graph-exchange SPMM, SDDMM approach (i),
monolithic all-gather SPMM) for the benchmark comparisons.

Mesh geometry: ("data", "model") == DEAL's (P, M) grid.  All collectives
are explicit jax.lax calls so the communication schedule is exactly the
paper's: ring ppermute of requested feature rows (SPMM), two tiled
all-to-alls (GEMM), edge-scalar psum (SDDMM approach (ii)).

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

from repro.core.partition import LayerPlan
from repro.kernels.ref import F32_MATMUL
from repro.kernels.ref import gemm_ref as ref_gemm
from repro.kernels.ref import sddmm_ref as ref_sddmm
from repro.kernels.ref import spmm_ref as ref_spmm

__all__ = [
    "make_gemm", "make_spmm", "make_spmm_p", "make_sddmm", "make_sddmm_p",
    "plan_device_arrays", "ref_gemm", "ref_spmm", "ref_sddmm",
]


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
        fn, mesh=mesh, in_specs=(P("data", "model"), P(None, None)),
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


def _accumulate(out, w, buf, dst, slot, pos, mask):
    vals = jnp.take(buf, pos, axis=0).astype(jnp.float32)
    vals = vals * (w[dst, slot] * mask).astype(jnp.float32)[:, None]
    return out.at[dst].add(vals)


def _spmm_deal_local(H, w, send_local, edge_dst, edge_slot, edge_pos,
                     edge_mask, *, P_: int, grouped: bool = True):
    """DEAL SPMM: ship only requested unique rows; grouped accumulation.

    H (u_loc, d_loc) source rows; w (r_loc, F) edge weights — output rows
    follow w, so a frontier subset (r_loc < u_loc) runs through the same
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
        vals = buf.astype(jnp.float32) * \
            (w[edge_dst[k], edge_slot[k]] * edge_mask[k]).astype(
                jnp.float32)[:, None]
        out = out.at[edge_dst[k]].add(vals)
    return out.astype(H.dtype)


def _squeeze0(x):
    return x[0]


def make_spmm_p(mesh, P_: int, variant: str = "deal",
                grouped: bool = True):
    """Jitted SPMM keyed on static geometry only (P, variant, grouped);
    the per-layer plan tensors are runtime arguments, so one compiled
    function serves every layer and every frontier-subset plan."""
    plan_spec = P("data", None, None)

    if variant == "allgather":
        def fn(H, w, nbr, mask):
            return _spmm_allgather_local(H, w, nbr[0], mask[0], P_=P_)
        return jax.jit(jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P("data", "model"), P("data", None),
                      P("data", None, None), P("data", None, None)),
            out_specs=P("data", "model")))

    if variant == "graph_exchange":
        def fn(H, w, mirror_src, edge_dst, edge_slot, edge_mask):
            return _spmm_graph_exchange_local(
                H, w, mirror_src[0], edge_dst[0], edge_slot[0],
                edge_mask[0], P_=P_)
        return jax.jit(jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P("data", "model"), P("data", None)) +
            (plan_spec,) * 4,
            out_specs=P("data", "model")))

    def fn(H, w, send_local, edge_dst, edge_slot, edge_pos, edge_mask):
        return _spmm_deal_local(
            H, w, send_local[0], edge_dst[0], edge_slot[0], edge_pos[0],
            edge_mask[0], P_=P_, grouped=grouped)
    return jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P("data", "model"), P("data", None)) + (plan_spec,) * 5,
        out_specs=P("data", "model")))


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
        fn, mesh=mesh,
        in_specs=(P("data", "model"), P("data", "model")) + (plan_spec,) * 5,
        out_specs=P("data", None), check_vma=(variant == "deal")))


def make_sddmm(mesh, lp: LayerPlan, variant: str = "deal"):
    return make_sddmm_p(mesh, lp.P, lp.fanout, variant)


# ----------------------------------------------------------------------
# single-host references: re-exported from kernels.ref (see module
# docstring) — ref_gemm / ref_spmm / ref_sddmm are bound in the imports.
# ----------------------------------------------------------------------

def plan_device_arrays(lp: LayerPlan) -> Dict[str, Any]:
    """The per-layer plan tensors shipped to devices (leading dim = P,
    sharded over `data`)."""
    return {
        "send_local": jnp.asarray(lp.send_local),
        "edge_dst": jnp.asarray(lp.edge_dst),
        "edge_slot": jnp.asarray(lp.edge_slot),
        "edge_pos": jnp.asarray(lp.edge_pos),
        "edge_mask": jnp.asarray(lp.edge_mask),
        "mirror_src": jnp.asarray(lp.mirror_src),
    }
