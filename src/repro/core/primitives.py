"""DEAL's distributed GNN primitives (§3.4) in shard_map, plus the paper's
baselines (CAGNET-style GEMM, graph-exchange SPMM, SDDMM approach (i),
monolithic all-gather SPMM) for the benchmark comparisons.

Mesh geometry: ("data", "model") == DEAL's (P, M) grid.  All collectives
are explicit jax.lax calls so the communication schedule is exactly the
paper's: ring ppermute of requested feature rows (SPMM), two tiled
all-to-alls (GEMM), edge-scalar psum (SDDMM approach (ii)).

The Deal consumers read the CommPlan as a dense (row, slot) table
(``LayerPlan.slot_src``): each masked-in fanout slot has one source row,
in the local tile or in one ring buffer, so the SPMM, SDDMM and attention
gather every slot's row once from [local tile; ring buffers 1..P-1] and
reduce over the fanout in slot order.  No consumer scatters, and a row's
bits are the same under a full-graph plan and a row-subset plan.

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

The Deal primitives are consumed through ``core.ops.DistExecutor`` (the
distributed backend of the executor layer); the baseline variants serve
the paper's comparisons only (``benchmarks/bench_primitives_dist.py``
and the distributed tests).  The ``make_*_p``
factories build jitted shard_map calls keyed only on static geometry
(P, variant, heads) so one compiled function serves every layer — and
every row-subset refresh — with the same shapes.  The plans are runtime
arguments ``(mask, send_local, slot_src)``, so full-graph plans
(``core.partition.build_plan``) and frontier-subset plans
(``build_subset_plan``) flow through the same compiled collectives.

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
    "head_layout", "make_gemm", "make_gat_attention_p", "make_rows",
    "make_spmm", "make_spmm_p", "make_sddmm", "make_sddmm_p",
    "plan_device_arrays",
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
    communication schedule."""
    bufs = []
    for k in range(1, P_):
        rows = jnp.take(H, send_local[k], axis=0)
        perm = [(i, (i - k) % P_) for i in range(P_)]
        bufs.append(jax.lax.ppermute(rows, "data", perm))
    return bufs


def _slot_rows(H, send_local, slot_src, P_: int):
    """For each fanout slot f, the (r, d_loc) f32 rows that the target
    rows' slot f reads through the plan's slot table from [H; ring
    buffers 1..P-1]: one gather per slot."""
    table = jnp.concatenate([H] + _ring_bufs(H, send_local, P_), axis=0)
    # every id is a row of the table by construction: clip, not fill
    return [jnp.take(table, slot_src[:, f], axis=0,
                     mode="clip").astype(jnp.float32)
            for f in range(slot_src.shape[1])]


def _weigh(vals, w):
    """Rows ``vals`` (..., d_loc) times their weights: (...) ones, or for
    head-major (..., c) ones, lane block j of the d_loc lanes times
    ``w[..., j]`` — selected per lane, since splitting the lanes into
    (c, d_loc / c) would pad each block to a whole lane tile."""
    if w.ndim < vals.ndim:
        return vals * w[..., None]
    c = w.shape[-1]
    head = jnp.arange(vals.shape[-1]) // (vals.shape[-1] // c)
    lane_w = w[..., :1]
    for j in range(1, c):
        lane_w = jnp.where(head == j, w[..., j:j + 1], lane_w)
    return vals * lane_w


def _masked(w, mask):
    """f32 edge weights with masked-out slots zeroed: ``mask`` has w's
    leading axes, and a head-major w one more."""
    m = mask if w.ndim == mask.ndim else mask[..., None]
    return (w * m).astype(jnp.float32)


def _spmm_deal_local(H, w, mask, send_local, slot_src, *, P_: int):
    """DEAL SPMM: ship only requested unique rows, then read each output
    row's F slots through the plan's slot table and add them in slot
    order — a gather and a sum, no scatter.

    H (u_loc, d_loc) source rows; w (r_loc, F) edge weights, or
    head-major (r_loc, F, c) ones (``_weigh``); mask (r_loc, F).  Output
    rows follow w, so a frontier subset (r_loc < u_loc) runs through the
    same compiled collective as the full graph (r_loc == u_loc), and a
    row's bits do not depend on which plan produced it.  Plan arrays
    squeezed to this device: send_local (P, R), slot_src (r_loc, F).
    """
    w = _masked(w, mask)
    rows = _slot_rows(H, send_local, slot_src, P_)
    out = _weigh(rows[0], w[:, 0])
    for f in range(1, len(rows)):
        out = out + _weigh(rows[f], w[:, f])
    return out.astype(H.dtype)


def _spmm_allgather_local(H, w, nbr, mask, *, P_: int):
    """Graph-partition-only baseline (Fig 3b): all-gather the FULL feature
    tile over `data` then gather locally — the memory blowup DEAL avoids."""
    full = jax.lax.all_gather(H, "data", axis=0, tiled=True)  # (N, d_loc)
    vals = jnp.take(full, nbr.reshape(-1), axis=0).astype(jnp.float32)
    vals = vals.reshape(nbr.shape + (H.shape[1],))
    out = (vals * (w * mask).astype(jnp.float32)[..., None]).sum(axis=1)
    return out.astype(H.dtype)


def _accumulate(out, w, vals, dst, slot, mask):
    return out.at[dst].add(_weigh(vals.astype(jnp.float32),
                                  _masked(w[dst, slot], mask)))


def _spmm_graph_exchange_local(H, w, mirror_src, edge_dst, edge_slot,
                               edge_mask, *, P_: int):
    """'Exchange G0' baseline (§3.4): the SOURCE owner gathers per-edge rows
    (duplicates included) and ships them to the destination — Z x more
    traffic than DEAL's unique-row exchange."""
    d_loc = H.shape[1]
    out = jnp.zeros((w.shape[0], d_loc), jnp.float32)
    # k=0: mirror_src == local row ids for the local group
    out = _accumulate(out, w, jnp.take(H, mirror_src[0], axis=0),
                      edge_dst[0], edge_slot[0], edge_mask[0])
    for k in range(1, P_):
        contrib = jnp.take(H, mirror_src[k], axis=0)       # (E, d_loc) dup!
        perm = [(i, (i - k) % P_) for i in range(P_)]
        buf = jax.lax.ppermute(contrib, "data", perm)
        out = _accumulate(out, w, buf, edge_dst[k], edge_slot[k],
                          edge_mask[k])
    return out.astype(H.dtype)


def make_spmm_p(mesh, P_: int, variant: str = "deal"):
    """Jitted SPMM keyed on static geometry only (P, variant); the
    per-layer plan tensors are runtime arguments, so one compiled
    function serves every layer and every frontier-subset plan.

    The deal variant takes ``(H, w, mask, send_local, slot_src)``: all
    ring sends go out first, then one consumer reads the local tile and
    every ring buffer through the slot table (the paper's grouped,
    per-step consumer (Fig 12c) would gather the whole table once per
    ring step).

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
        plan_specs = (plan_spec,) * 4
    else:
        def fn(H, w, mask, send_local, slot_src):
            return _spmm_deal_local(H, w, mask, send_local[0], slot_src[0],
                                    P_=P_)
        plan_specs = (P("data", None), plan_spec, plan_spec)

    def program(w_spec):
        return jax.jit(jax.shard_map(
            _named(fn, "dist_spmm"), mesh=mesh,
            in_specs=(P("data", "model"), w_spec) + plan_specs,
            out_specs=P("data", "model")))

    per_edge = program(P("data", None))
    head_major = program(P("data", None, "model"))

    def spmm(H, w, *plan):
        return (head_major if w.ndim == 3 else per_edge)(H, w, *plan)
    return spmm


def make_spmm(mesh, lp: LayerPlan, variant: str = "deal"):
    return make_spmm_p(mesh, lp.P, variant)


# ----------------------------------------------------------------------
# SDDMM
# ----------------------------------------------------------------------

def _slot_scores(q, kf, mask, send_local, slot_src, *, P_: int, c: int):
    """(r, F, c) dots of each row's own q with the k rows its slots read,
    over each of the c lane blocks (sliced, not reshaped: see
    ``_weigh``), zero on masked-out slots."""
    dh = q.shape[1] // c
    qf = q.astype(jnp.float32)

    def scores(rows):
        prod = rows * qf
        return jnp.stack([prod[:, j * dh:(j + 1) * dh].sum(-1)
                          for j in range(c)], axis=-1)
    return jnp.stack([scores(rows) for rows in
                      _slot_rows(kf, send_local, slot_src, P_)],
                     axis=1) * mask[..., None]


def _sddmm_deal_local(q, kf, mask, send_local, slot_src, *, P_: int):
    """Approach (ii): partial dots over this device's D/M slice, then psum
    the edge SCALARS over `model` (exchange results, not features)."""
    attn = _slot_scores(q, kf, mask, send_local, slot_src, P_=P_, c=1)
    return jax.lax.psum(attn[..., 0], "model")


def _sddmm_dup_local(q, kf, mask, send_local, slot_src, *, P_: int):
    """Approach (i): all-gather the FULL feature columns over `model`
    (duplicate the computation), no result exchange."""
    qf = jax.lax.all_gather(q, "model", axis=1, tiled=True)   # (n_loc, D)
    kff = jax.lax.all_gather(kf, "model", axis=1, tiled=True)
    return _slot_scores(qf, kff, mask, send_local, slot_src, P_=P_,
                        c=1)[..., 0]


def make_sddmm_p(mesh, P_: int, variant: str = "deal"):
    """Jitted SDDMM ``fn(q, kf, mask, send_local, slot_src)`` -> (N, F)
    keyed on static geometry only (P, variant) — see ``make_spmm_p``."""
    local = _sddmm_deal_local if variant == "deal" else _sddmm_dup_local
    plan_spec = P("data", None, None)

    def fn(q, kf, mask, send_local, slot_src):
        return local(q, kf, mask, send_local[0], slot_src[0], P_=P_)
    # approach (i) duplicates the computation, so its output is replicated
    # over `model` by construction — not statically inferable (check_vma).
    return jax.jit(jax.shard_map(
        _named(fn, "dist_sddmm"), mesh=mesh,
        in_specs=(P("data", "model"), P("data", "model"), P("data", None),
                  plan_spec, plan_spec),
        out_specs=P("data", None), check_vma=(variant == "deal")))


def make_sddmm(mesh, lp: LayerPlan, variant: str = "deal"):
    return make_sddmm_p(mesh, lp.P, variant)


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


def _head_scores_local(q, kf, mask, send_local, slot_src, *, P_: int,
                       heads: int, M: int):
    """Per-head dot scores (n_loc, F, c) from this shard's lanes of q
    and of the exchanged k rows, zero on masked-out slots.  With whole
    heads here (c = heads / M) no collective; a head spanning M / heads
    shards psums its partial edge scalars over ``model`` (approach
    (ii)), c = heads."""
    attn = _slot_scores(q, kf, mask, send_local, slot_src, P_=P_,
                        c=head_layout(heads, M))
    if heads >= M:
        return attn
    full = jnp.zeros(attn.shape[:2] + (heads,), jnp.float32)
    head = jax.lax.axis_index("model") // (M // heads)
    full = jax.lax.dynamic_update_slice_in_dim(full, attn, head, axis=2)
    return jax.lax.psum(full, "model")


def _gat_attention_local(q, kf, mask, *plan, P_: int, heads: int, M: int):
    dh = q.shape[1] * M // heads
    s = _head_scores_local(q, kf, mask, *plan, P_=P_, heads=heads,
                           M=M) / jnp.sqrt(jnp.float32(dh))
    return edge_softmax(s, mask > 0)


def make_gat_attention_p(mesh, P_: int, heads: int):
    """Jitted per-head attention keyed on static geometry (P, heads):
    ``fn(q, kf, mask_f, send_local, slot_src)`` -> (N, F, heads), the
    scaled scores normalized over each row's masked-in slots.  Sharded
    over ``model`` on the heads axis where each shard owns whole heads,
    replicated over it where a head spans shards.  Named
    ``dist_gat_attention``."""
    M = mesh.shape["model"]
    head_layout(heads, M)               # raises on a pair it cannot split
    plan_spec = P("data", None, None)

    def fn(q, kf, mask, send_local, slot_src):
        return _gat_attention_local(q, kf, mask, send_local[0], slot_src[0],
                                    P_=P_, heads=heads, M=M)
    return jax.jit(jax.shard_map(
        _named(fn, "dist_gat_attention"), mesh=mesh,
        in_specs=(P("data", "model"), P("data", "model"),
                  P("data", None), plan_spec, plan_spec),
        out_specs=P("data", None, "model" if heads >= M else None)))


# ----------------------------------------------------------------------
# whole rows for the host copy
# ----------------------------------------------------------------------

def _rows_local(H):
    """(n/P, D/M) column shard -> (n/(P*M), D) whole rows: each device
    sends the i-th of M row slices of its shard to ``model`` peer i and
    lays the M column pieces it receives side by side."""
    return jax.lax.all_to_all(H, "model", 0, 1, tiled=True)


def make_rows(mesh):
    """Jitted relayout of a (data, model) column-sharded (n, D) array
    into P*M blocks of whole rows, ``P(("data", "model"), None)``: no
    replica, and each block one contiguous run of rows.  No arithmetic:
    the values are the input's bits.  Named ``dist_rows``."""
    return jax.jit(jax.shard_map(
        _named(_rows_local, "dist_rows"), mesh=mesh,
        in_specs=(P("data", "model"),),
        out_specs=P(("data", "model"), None)))


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
            for name in ("send_local", "slot_src", "edge_dst", "edge_slot",
                         "edge_mask", "mirror_src")}
