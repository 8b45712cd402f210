"""The pluggable layer-op executor layer (InferTurbo-style retargeting).

One layer's semantics — the GEMM -> SPMM / SDDMM dataflow over a sampled
layer graph (Deal §3.4) — is declared once per model in
``gnn_models.model_spec`` and executed here against one of three
interchangeable backends:

  ``RefExecutor``     pure-jnp oracle (the ``kernels.ref`` primitives);
                      bitwise-identical to the pre-executor engines.
  ``PallasExecutor``  the Pallas SPMM/SDDMM kernels from ``kernels/``:
                      compiled on TPU, interpret mode elsewhere.  Pads
                      rows/columns to kernel block multiples internally,
                      so non-aligned N/D shapes just work.
  ``DistExecutor``    the §3.4 shard_map primitives on a (data, model)
                      mesh with the static CommPlan — plus a ROW-SUBSET
                      mode (``run_rows``) that executes one layer for a
                      frontier of rows with a per-partition frontier
                      split (the ROADMAP "distributed delta refresh").

Executor primitives take a graph binding ``io`` object:
``DenseIO`` (neighbor matrix + mask indexing the source rows directly)
for the single-host executors, ``DistIO`` (plan tensors + sharded edge
weights) for the mesh.  ``run_layer`` interprets a ``LayerSpec`` over an
executor; ``run_model`` drives a whole forward pass.  The source slot
``h_src`` and target slot ``h_tgt`` decouple so the same spec serves
full-graph inference (h_src is h_tgt) and delta refresh (h_src is the
gathered universe) — see ``gnnserve.delta``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs, tuning
from repro.api.registry import EXECUTORS, register_executor
from repro.core import primitives as prim
from repro.core.gnn_models import (LayerSpec, ModelSpec, edge_softmax,
                                   gat_head_scores, mean_weights)
from repro.core.partition import build_plan, build_subset_plan_cached
from repro.core.sampler import LayerGraph
from repro.kernels import ops as kops
from repro.kernels.ref import F32_MATMUL
from repro.kernels.spmm import auto_block_n


# ----------------------------------------------------------------------
# graph bindings
# ----------------------------------------------------------------------

class DenseIO:
    """Graph binding for the single-host executors: a fixed-fanout
    neighbor matrix whose ids index the spmm/sddmm source rows directly
    (global ids in full-graph mode, universe positions in delta mode).

    An optional ``table`` adds one level of indirection — the ids in
    ``nbr`` index ``table`` and ``table[id]`` indexes the source rows
    (loader order in §3.5 fused feature prep, universe positions in
    delta refresh).  Executors with a fused gather kernel consume
    ``table`` directly; everything else reads ``nbr_resolved``, which
    materializes the translation lazily (and is bitwise-identical, so
    the two routes interchange freely)."""

    def __init__(self, nbr: np.ndarray, mask: np.ndarray, table=None):
        self.nbr_np = np.asarray(nbr)
        self.mask_np = np.asarray(mask)
        self.nbr = jnp.asarray(self.nbr_np)
        self.mask = jnp.asarray(self.mask_np)
        self.table = (None if table is None
                      else jnp.asarray(table, jnp.int32))
        self._nbr_resolved = None
        self._mean_w = None

    @classmethod
    def from_layer_graph(cls, lg: LayerGraph) -> "DenseIO":
        return cls(lg.nbr, lg.mask)

    @property
    def nbr_resolved(self):
        """``nbr`` with the table indirection applied (identity when no
        table) — the materialized-gather fallback path."""
        if self.table is None:
            return self.nbr
        if self._nbr_resolved is None:
            self._nbr_resolved = jnp.take(
                self.table, self.nbr.reshape(-1)).reshape(self.nbr.shape)
        return self._nbr_resolved

    @property
    def mean_w(self):
        """Mean-aggregation edge weights (computed lazily: gat never
        reads them)."""
        if self._mean_w is None:
            with obs.span("infer.mean_w"):
                self._mean_w = jnp.asarray(mean_weights(self.mask_np))
        return self._mean_w


@dataclasses.dataclass
class DistIO:
    """Graph binding for DistExecutor: the jitted collectives plus the
    plan tensors they consume, and the sharded per-row edge weights.
    ``args`` follows the spmm variant's signature; ``sddmm_args`` is
    always the deal-style 5-tuple the SDDMM collective expects."""
    spmm: Callable
    args: Tuple                      # plan arrays, sharded over "data"
    mean_w: Any                      # (N, F) mean weights, row-sharded
    mask_f: Any                      # (N, F) float mask, row-sharded (gat)
    sddmm: Optional[Callable] = None
    sddmm_args: Tuple = ()


# ----------------------------------------------------------------------
# spec interpreter
# ----------------------------------------------------------------------

def _fusable_attn_pair(ex, layer: LayerSpec, i: int) -> bool:
    """True when ops[i] is an (attn_scores -> edge_softmax) pair the
    executor can collapse into one ``attn_scores_softmax`` call: the
    softmax must be the ONLY consumer of the raw scores (they are never
    materialized on the fused path)."""
    ops = layer.ops
    if (getattr(ex, "attn_scores_softmax", None) is None
            or ops[i].kind != "attn_scores" or i + 1 >= len(ops)
            or ops[i + 1].kind != "edge_softmax"
            or ops[i + 1].src[0] != ops[i].out):
        return False
    readers = [op for j, op in enumerate(ops)
               if j != i + 1 and ops[i].out in op.src]
    return not readers and layer.out != ops[i].out


def run_layer(ex, layer: LayerSpec, io, h_tgt, h_src, heads: int = 1):
    """Execute one LayerSpec.  ``h_tgt``/``h_src`` may be zero-arg
    callables, resolved on first use (delta refresh reads target rows
    from the store only for models that reference them).

    Peephole: an (attn_scores -> edge_softmax) pair collapses into one
    ``attn_scores_softmax`` call when the executor exposes it (the
    fused SDDMM+softmax kernel) — the (N, F) score tensor never
    round-trips through HBM."""
    env: Dict[str, Any] = {"h_tgt": h_tgt, "h_src": h_src}

    def get(name):
        v = env[name]
        if callable(v):
            v = v()
            env[name] = v
        return v

    skip = -1
    for i, op in enumerate(layer.ops):
        if i == skip:
            continue
        kind = op.kind
        out_slot = op.out
        if _fusable_attn_pair(ex, layer, i):
            kind = "attn_scores_softmax"
            out_slot = layer.ops[i + 1].out
            skip = i + 1
        with obs.span("ops." + kind) as sp:
            if kind == "gemm":
                out = ex.gemm(get(op.src[0]), op.param)
            elif kind == "spmm":
                out = ex.spmm(get(op.src[0]), io.mean_w, io)
            elif kind == "add":
                out = get(op.src[0]) + get(op.src[1])
            elif kind == "attn_scores":
                out = ex.attn_scores(get(op.src[0]), get(op.src[1]), io,
                                     heads)
            elif kind == "attn_scores_softmax":
                out = ex.attn_scores_softmax(get(op.src[0]),
                                             get(op.src[1]), io, heads)
            elif kind == "edge_softmax":
                out = ex.edge_softmax(get(op.src[0]), io)
            elif kind == "attend":
                out = ex.attend(get(op.src[0]), get(op.src[1]), io, heads)
            else:
                raise ValueError(f"unknown layer op {kind!r}")
            if sp:
                if not obs.profiling():
                    # make the span's host time stand for device time;
                    # under a profiler the trace has the device's own
                    out = jax.block_until_ready(out)
                sp.set(executor=getattr(ex, "name", type(ex).__name__),
                       rows=int(out.shape[0]))
        env[out_slot] = out
    return env[layer.out]


def run_model(ex, spec: ModelSpec, ios: Sequence, X,
              activation: Optional[Callable] = None):
    """Full forward pass: layer l reads/writes the same row set
    (h_src == h_tgt == H), activation between layers."""
    act = activation or spec.activation
    with obs.span("model.prepare"):
        H = ex.prepare(X)
    L = len(spec.layers)
    for l, layer in enumerate(spec.layers):
        H = run_layer(ex, layer, ios[l], H, H, spec.heads)
        if l < L - 1:
            with obs.span("ops.activation"):
                H = act(H)
    return H


# ----------------------------------------------------------------------
# RefExecutor — the jnp oracle
# ----------------------------------------------------------------------

class RefExecutor:
    """Single-host pure-jnp backend; op-for-op the pre-refactor
    ``local_*_infer`` / delta math, so outputs are bitwise-preserved."""

    name = "ref"

    def prepare(self, X):
        return jnp.asarray(X)

    def gemm(self, H, W):
        return prim.ref_gemm(H, jnp.asarray(W))

    def spmm(self, H_src, w_edge, io: DenseIO):
        return prim.ref_spmm(H_src, w_edge, io.nbr_resolved, io.mask)

    def attn_scores(self, q, k, io: DenseIO, heads: int):
        """Per-head scaled dot scores (R, F, h); k rows may outnumber q
        rows (universe gather)."""
        return gat_head_scores(q, k, io.nbr_resolved, io.mask, heads)

    def edge_softmax(self, s, io: DenseIO):
        return edge_softmax(s, io.mask)

    def attend(self, alpha, v, io: DenseIO, heads: int):
        D = v.shape[-1]
        dh = D // heads
        vn = jnp.take(v.reshape(-1, heads, dh),
                      io.nbr_resolved.reshape(-1),
                      axis=0).reshape(io.nbr.shape + (heads, dh))
        return jnp.einsum("nfh,nfhd->nhd", alpha, vn,
                          precision=F32_MATMUL).reshape(alpha.shape[0], D)


# ----------------------------------------------------------------------
# PallasExecutor — the kernels in kernels/ (compiled on TPU)
# ----------------------------------------------------------------------

def pad_to_blocks(block_n: int, nbr, mask, *row_arrays):
    """Pad the leading (row) axis of graph-shaped arrays to the next
    ``block_n`` multiple — the ONE pad-to-block helper every Pallas
    call site shares.  ``nbr`` pads with 0 (a valid in-range id) and
    ``mask`` with False, so padded slots contribute exactly 0.0 and the
    output slice-back is value-neutral.  Returns (Rp, nbr, mask,
    *row_arrays) with every extra array zero-padded the same way."""
    R = nbr.shape[0]
    Rp = -(-R // block_n) * block_n

    def pad(a, fill=0):
        if a.shape[0] == Rp:
            return a
        widths = [(0, Rp - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, widths, constant_values=fill)

    return (Rp, pad(nbr), pad(mask, fill=False)) + tuple(
        pad(a) for a in row_arrays)


class PallasExecutor(RefExecutor):
    """Routes spmm/sddmm through the Pallas kernels (``kernels.ops``
    dispatch: compiled on TPU, interpret mode elsewhere).  GEMM stays on
    XLA's MXU path — a hand-written matmul kernel would only lose.
    Rows are padded to block multiples here and feature columns to whole
    lane tiles inside the kernels, then sliced back — non-aligned shapes
    just work.

    ``fused_gather``: consume ``DenseIO.table`` via the fused
    gather+spmm kernel instead of materializing ``nbr_resolved``
    (bitwise-identical — same per-row accumulation order, masked slots
    multiply by exact 0.0).  ``fused_attention``: collapse GAT's
    attn_scores -> edge_softmax into the one-pass SDDMM+softmax kernel
    (all heads per call, no HBM score round-trip) via the ``run_layer``
    peephole.  ``block_table``: a ``tuning.BlockTable`` source
    ("default" = configs/tuned_blocks.json) consulted per (kernel,
    shape-bucket, dtype) at bind time; block sizes never change the
    per-row accumulation order, so tuned vs untuned is bitwise too.
    ``block_n=None`` auto-sizes from the padded row count.
    """

    name = "pallas"

    def __init__(self, block_n: Optional[int] = None, block_d: int = 128,
                 use_kernel: bool = True, fused_gather: bool = True,
                 fused_attention: bool = True, block_table=None):
        self.block_n = block_n
        self.block_d = block_d
        self.use_kernel = use_kernel
        self.fused_gather = fused_gather
        self.fused_attention = fused_attention
        self._blocks = tuning.resolve_block_table(block_table)
        self._block_memo: Dict[Tuple, Tuple] = {}

    def _pick_blocks(self, kernel: str, R: int, D: int,
                     dtype) -> Tuple[Optional[int], int]:
        """(block_n, block_d) for one call site: tuned table entry if
        bound, else the constructor values (block_n None -> auto)."""
        key = (kernel, tuning.shape_bucket(R), tuning.shape_bucket(D),
               jnp.dtype(dtype).name)
        got = self._block_memo.get(key)
        if got is None:
            tuned = {}
            if self._blocks is not None:
                tuned = self._blocks.lookup(kernel, N=R, D=D,
                                            dtype=key[3]) or {}
            got = (tuned.get("block_n", self.block_n),
                   tuned.get("block_d", self.block_d))
            self._block_memo[key] = got
        return got

    def _row_block(self, bn: Optional[int], R: int) -> Tuple[int, int]:
        """(pad multiple, kernel row block).  An explicit/tuned block is
        both; None pads to the f32 sublane tile (8) and lets
        ``auto_block_n`` take the largest divisor of the padded count."""
        if bn is not None:
            return bn, bn
        Rp = -(-R // 8) * 8
        return 8, auto_block_n(Rp)

    def _spmm_kernel(self, H_src, w_edge, nbr, mask, table=None):
        R, F = nbr.shape
        D = H_src.shape[1]
        kernel = "gather_spmm" if table is not None else "spmm"
        bn, bd = self._pick_blocks(kernel, R, D, H_src.dtype)
        pad_n, block_n = self._row_block(bn, R)
        _, nbr, mask, w_edge = pad_to_blocks(pad_n, nbr, mask, w_edge)
        if w_edge.ndim == 3:        # (R, F, heads) -> head-major
            w_edge = w_edge.transpose(2, 0, 1)
        if table is not None:
            out = kops.gather_spmm(H_src, table, w_edge, nbr, mask,
                                   use_kernel=self.use_kernel,
                                   block_n=block_n, block_d=bd)
        else:
            out = kops.spmm(H_src, w_edge, nbr, mask,
                            use_kernel=self.use_kernel,
                            block_n=block_n, block_d=bd)
        return out[:R]

    def spmm(self, H_src, w_edge, io: DenseIO):
        if self.fused_gather and io.table is not None:
            return self._spmm_kernel(H_src, w_edge, io.nbr, io.mask,
                                     table=io.table)
        return self._spmm_kernel(H_src, w_edge, io.nbr_resolved, io.mask)

    def attn_scores(self, q, k, io: DenseIO, heads: int):
        """Per-head SDDMM kernel calls over head-major column slices
        (the UNFUSED score path — kept for specs that consume raw
        scores; the peephole routes GAT through
        ``attn_scores_softmax``)."""
        R = io.nbr.shape[0]
        D = q.shape[1]
        dh = D // heads
        bn, _ = self._pick_blocks("sddmm", R, dh, q.dtype)
        pad_n, block_n = self._row_block(bn, R)
        _, nbr, mask, qp = pad_to_blocks(pad_n, io.nbr_resolved, io.mask,
                                         q)
        per_head = [kops.sddmm(qp[:, h * dh:(h + 1) * dh],
                               k[:, h * dh:(h + 1) * dh], nbr, mask,
                               use_kernel=self.use_kernel,
                               block_n=block_n)
                    for h in range(heads)]
        s = jnp.stack(per_head, axis=-1)[:R]            # (R, F, h)
        return s / jnp.sqrt(jnp.float32(dh))

    @property
    def attn_scores_softmax(self):
        """Fused SDDMM + masked-softmax entry the ``run_layer`` peephole
        probes for; None (= disabled) when fusion is off."""
        if not self.fused_attention:
            return None
        return self._attn_scores_softmax

    def _attn_scores_softmax(self, q, k, io: DenseIO, heads: int):
        R = io.nbr.shape[0]
        D = q.shape[1]
        bn, _ = self._pick_blocks("gat_attention", R, D // heads,
                                  q.dtype)
        pad_n, block_n = self._row_block(bn, R)
        _, nbr, mask, qp = pad_to_blocks(pad_n, io.nbr_resolved, io.mask,
                                         q)
        alpha = kops.gat_attention(qp, k, nbr, mask, heads=heads,
                                   use_kernel=self.use_kernel,
                                   block_n=block_n)
        return alpha[:R]

    def attend(self, alpha, v, io: DenseIO, heads: int):
        """One kernel call for all heads: each edge's full row of ``v`` is
        gathered once and lane c weighted by head c // dh's alpha."""
        obs.add("pallas.attend_calls")
        obs.add("pallas.attend_kernel_calls")
        return self.spmm(v, alpha[..., 0] if heads == 1 else alpha, io)


# ----------------------------------------------------------------------
# DistExecutor — shard_map primitives + CommPlan, full or row-subset
# ----------------------------------------------------------------------

class DistExecutor:
    """Deal's distributed backend on a ("data", "model") mesh.

    Full-graph mode: ``bind`` builds the static CommPlan for a list of
    layer graphs and returns per-layer ``DistIO``s.  Row-subset mode:
    ``run_rows`` executes ONE layer for a frontier of rows, splitting
    the frontier per partition by the same 1-D ownership as the full
    plan — per-row reduction order (and hence bitwise output) matches a
    full epoch through this executor.

    GAT: heads split over ``model`` (``primitives.head_layout``).  With
    heads % M == 0 each shard scores, normalizes and attends its own
    whole heads (``dist_gat_attention``, no score collective); with
    M % heads == 0 a head spans M / heads shards, which psum its partial
    edge scores.  ``heads == 1`` keeps the one full-width dot over all D
    lanes (psum over ``model``) and the (N, F) scores of the
    pre-refactor distributed engine, bit for bit.  Other (heads, M)
    pairs raise; ``DealConfig.validate`` names them first.
    """

    name = "dist"

    def __init__(self, mesh, *, spmm_variant: str = "deal",
                 gemm_variant: str = "deal", sddmm_variant: str = "deal",
                 grouped: bool = True, subset_floor: int = 64):
        self.mesh = mesh
        self.P = mesh.shape["data"]
        self.M = mesh.shape["model"]
        # pow2-bucket floor for row-subset plans: higher = fewer compiled
        # shapes across refreshes, more padded compute per refresh
        self.subset_floor = subset_floor
        self.spmm_variant = spmm_variant
        self.sddmm_variant = sddmm_variant
        self._gemm = prim.make_gemm(mesh, gemm_variant)
        self._spmm = prim.make_spmm_p(mesh, self.P, spmm_variant, grouped)
        self._sddmm_cache: Dict[int, Callable] = {}
        self._attn_cache: Dict[Tuple[int, int, bool], Callable] = {}
        self._row_spec = NamedSharding(mesh, P("data", None))
        self._hd_spec = NamedSharding(mesh, P("data", "model"))
        self._plan_spec = NamedSharding(mesh, P("data", None, None))
        self.plan = None

    # -- plumbing -------------------------------------------------------
    def _put(self, x, spec):
        return jax.device_put(x, spec)

    def _sddmm_fn(self, fanout: int) -> Callable:
        if fanout not in self._sddmm_cache:
            self._sddmm_cache[fanout] = prim.make_sddmm_p(
                self.mesh, self.P, fanout, self.sddmm_variant)
        return self._sddmm_cache[fanout]

    def _attn_fn(self, fanout: int, heads: int, softmax: bool) -> Callable:
        key = (fanout, heads, softmax)
        if key not in self._attn_cache:
            if self.sddmm_variant != "deal":
                raise ValueError("multi-head attention on the mesh needs "
                                 "the deal SDDMM (approach (ii))")
            self._attn_cache[key] = prim.make_gat_attention_p(
                self.mesh, self.P, fanout, heads, softmax)
        obs.gauge("dist.attn_heads_local", heads / self.M)
        return self._attn_cache[key]

    def _deal_args(self, dev: Dict[str, Any]) -> Tuple:
        return (dev["send_local"], dev["edge_dst"], dev["edge_slot"],
                dev["edge_pos"], dev["edge_mask"])

    def _plan_args(self, dev: Dict[str, Any]) -> Tuple:
        if self.spmm_variant == "graph_exchange":
            return (dev["mirror_src"], dev["edge_dst"], dev["edge_slot"],
                    dev["edge_mask"])
        return self._deal_args(dev)

    # -- full-graph binding ---------------------------------------------
    def bind(self, layer_graphs: Sequence[LayerGraph],
             need_sddmm: bool = False) -> List[DistIO]:
        with obs.span("dist.bind") as bsp:
            self.plan = build_plan(list(layer_graphs), self.P, self.M)
            ios = []
            for l, lp in enumerate(self.plan.layers):
                lg = layer_graphs[l]
                obs.gauge(f"dist.ring_rows.layer{l}",
                          int(lp.send_count[:, 1:].sum()))
                dev = prim.plan_device_arrays(lp, self._plan_spec)
                ios.append(DistIO(
                    spmm=self._spmm,
                    args=self._plan_args(dev),
                    mean_w=self._put(mean_weights(lg.mask),
                                     self._row_spec),
                    mask_f=self._put(lg.mask.astype(np.float32),
                                     self._row_spec),
                    sddmm=self._sddmm_fn(lp.fanout) if need_sddmm
                    else None,
                    sddmm_args=self._deal_args(dev) if need_sddmm
                    else ()))
            if bsp:
                bsp.set(n_layers=len(ios), P=self.P, M=self.M)
        return ios

    # -- executor primitives --------------------------------------------
    def prepare(self, X):
        return self._put(X, self._hd_spec)

    def gemm(self, H, W):
        return self._gemm(H, jnp.asarray(W))

    def spmm(self, H_src, w_edge, io: DistIO):
        return io.spmm(H_src, w_edge, *io.args)

    def attn_scores(self, q, k, io: DistIO, heads: int):
        if heads == 1:
            scores = io.sddmm(q, k, *io.sddmm_args)
            D = q.shape[1]                   # full width (global array)
            return scores / np.sqrt(D)
        fn = self._attn_fn(io.mask_f.shape[1], heads, softmax=False)
        return fn(q, k, io.mask_f, *io.sddmm_args)

    def attn_scores_softmax(self, q, k, io: DistIO, heads: int):
        """Scores and edge softmax as one call (the ``run_layer``
        peephole): one shard_map for heads > 1; for one head, the
        unfused pair's own ops."""
        if heads == 1:
            return self.edge_softmax(self.attn_scores(q, k, io, 1), io)
        fn = self._attn_fn(io.mask_f.shape[1], heads, softmax=True)
        return fn(q, k, io.mask_f, *io.sddmm_args)

    def edge_softmax(self, s, io: DistIO):
        return edge_softmax(s, io.mask_f > 0)

    def attend(self, alpha, v, io: DistIO, heads: int):
        """The ring SPMM, each exchanged row weighted per head: (N, F,
        heads) weights go head-major to the model shards that hold each
        head's lanes (repeated where a head spans shards)."""
        if alpha.ndim == 3 and alpha.shape[-1] < self.M:
            alpha = jnp.repeat(alpha, self.M // alpha.shape[-1], axis=-1)
        return io.spmm(v, alpha, *io.args)

    # -- row-subset mode (distributed delta refresh) --------------------
    def run_rows(self, layer: LayerSpec, lg: LayerGraph, rows: np.ndarray,
                 read_level: Callable, level: int, heads: int = 1,
                 *, n_nodes: Optional[int] = None):
        """Execute ``layer`` for the sorted row subset ``rows``, frontier
        split per partition.  ``read_level(level, ids)`` supplies input
        rows (the store's staged view during a refresh).  Returns the
        (pre-activation) global padded output plus (take, n_src): the
        real-row indices into it and the universe-row work count.

        ``n_nodes`` pins the partition geometry to the pre-growth main
        range when the layer graph has an unfolded tail appended — every
        row (and masked neighbour) passed here must stay below it."""
        assert self.spmm_variant == "deal", \
            "row-subset mode needs the unique-row exchange plan"
        assert self.M & (self.M - 1) == 0, \
            "model axis must be a power of two (pad buckets)"
        with obs.span("dist.subset_plan") as psp:
            sp = build_subset_plan_cached(lg, rows, self.P,
                                          m_align=self.M,
                                          floor=self.subset_floor,
                                          n_nodes=n_nodes)
            if psp:
                psp.set(rows=int(rows.size), src_rows=int(sp.n_src_rows),
                        level=level)
        args = (jnp.asarray(sp.send_local), jnp.asarray(sp.edge_dst),
                jnp.asarray(sp.edge_slot), jnp.asarray(sp.edge_pos),
                jnp.asarray(sp.edge_mask))
        io = DistIO(
            spmm=self._spmm,
            args=args,
            sddmm_args=args,
            mean_w=self._put(
                mean_weights(sp.row_mask.reshape(-1, sp.fanout)),
                self._row_spec),
            mask_f=self._put(
                sp.row_mask.reshape(-1, sp.fanout).astype(np.float32),
                self._row_spec),
            sddmm=self._sddmm_fn(sp.fanout))
        with obs.span("dist.exchange") as xsp:
            src_rows = read_level(level, sp.src_ids.reshape(-1))
            H_src = self._put(src_rows, self._hd_spec)
            if xsp:
                nbytes = int(np.asarray(src_rows).nbytes)
                xsp.set(bytes=nbytes, rows=int(sp.n_src_rows),
                        level=level)
                obs.add("dist.exchanged_bytes", nbytes)
                obs.add("dist.src_rows", int(sp.n_src_rows))
        h_tgt = lambda: self._put(                       # noqa: E731
            read_level(level, sp.row_ids.reshape(-1)), self._hd_spec)
        H = run_layer(self, layer, io, h_tgt, H_src, heads)
        return H, sp.take, sp.n_src_rows


# ----------------------------------------------------------------------
# factory — backends resolve through the executor registry
# ----------------------------------------------------------------------

def _make_ref(mesh=None, **kw):
    return RefExecutor()


def _make_pallas(mesh=None, **kw):
    return PallasExecutor(**kw)


def _make_dist(mesh=None, **kw):
    if mesh is None:
        raise ValueError("dist executor needs a mesh= argument")
    return DistExecutor(mesh, **kw)


register_executor("ref", _make_ref)
register_executor("pallas", _make_pallas)
register_executor("dist", _make_dist)


def get_executor(executor="ref", *, mesh=None, **kw):
    """Resolve a REGISTERED executor name ("ref" | "pallas" | "dist" |
    anything added via ``api.registry.register_executor``) or pass an
    instance through.  "dist" needs a mesh.  Unknown names raise with
    every registered name listed."""
    if not isinstance(executor, str):
        return executor
    try:
        factory = EXECUTORS.get(executor)
    except KeyError as exc:
        raise ValueError(str(exc)) from None
    return factory(mesh=mesh, **kw)
