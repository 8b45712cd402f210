"""The layer-op executors: one layer program, three backends.

One layer's semantics — the GEMM -> SPMM / SDDMM dataflow over a sampled
layer graph (Deal §3.4) — is declared once per model in
``gnn_models.model_spec`` and executed here by one of three executors,
each with one path:

  ``RefExecutor``     pure-jnp oracle (the ``kernels.ref`` primitives).
  ``PallasExecutor``  the Pallas kernels from ``kernels/``: compiled on
                      TPU, interpret mode elsewhere.  Rows pad to the f32
                      sublane tile and columns to lane tiles internally,
                      so non-aligned N/D shapes just work.
  ``DistExecutor``    Deal's §3.4 shard_map primitives on a (data, model)
                      mesh with the static CommPlan — plus a ROW-SUBSET
                      mode (``run_rows``) that executes one layer for a
                      frontier of rows with a per-partition frontier
                      split (the distributed delta refresh).

Each executor binds a model's layer graphs itself: ``ex.bind(layer_graphs,
spec)`` returns one graph binding per layer of ``spec`` — ``DenseIO``
(neighbor matrix + mask indexing the source rows directly) for the
single-host executors, ``DistIO`` (plan tensors + sharded edge weights)
for the mesh.  ``run_layer`` interprets a ``LayerSpec`` over an
executor; ``run_model`` drives a whole forward pass, the one driver
every caller shares::

    H = run_model(ex, spec, ex.bind(layer_graphs, spec), X)

The source slot ``h_src`` and target slot ``h_tgt`` decouple so the same
spec serves full-graph inference (h_src is h_tgt) and delta refresh
(h_src is the gathered universe) — see ``gnnserve.delta``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.api.registry import EXECUTORS, register_executor
from repro.core import primitives as prim
from repro.core.gnn_models import (LayerSpec, ModelSpec, edge_softmax,
                                   gat_head_scores, mean_weights)
from repro.core.partition import build_plan, build_subset_plan_cached
from repro.core.sampler import LayerGraph
from repro.kernels import ops as kops
from repro.kernels.ref import F32_MATMUL

# pow2-bucket floor for row-subset plans: higher = fewer compiled shapes
# across refreshes, more padded compute per refresh
SUBSET_FLOOR = 64

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
    delta refresh).  ``PallasExecutor.spmm`` consumes ``table`` directly
    (the ``gather_spmm`` kernel); everything else reads
    ``nbr_resolved``, which materializes the translation lazily (and is
    bitwise-identical)."""

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
        table)."""
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
    """Graph binding for DistExecutor: the plan tensors every ring
    primitive reads, ``(mask_f, send_local, slot_src)`` sharded over
    "data", and the row-sharded per-edge mean weights."""
    args: Tuple                      # (mask_f, send_local, slot_src)
    mean_w: Any                      # (N, F) mean weights, row-sharded

    @property
    def mask_f(self):
        """(N, F) float mask, row-sharded."""
        return self.args[0]


# ----------------------------------------------------------------------
# spec interpreter
# ----------------------------------------------------------------------

def run_layer(ex, layer: LayerSpec, io, h_tgt, h_src, heads: int = 1):
    """Execute one LayerSpec.  ``h_tgt``/``h_src`` may be zero-arg
    callables, resolved on first use (delta refresh reads target rows
    from the store only for models that reference them)."""
    env: Dict[str, Any] = {"h_tgt": h_tgt, "h_src": h_src}

    def get(name):
        v = env[name]
        if callable(v):
            v = v()
            env[name] = v
        return v

    for op in layer.ops:
        kind = op.kind
        with obs.span("ops." + kind) as sp:
            if kind == "gemm":
                out = ex.gemm(get(op.src[0]), op.param)
            elif kind == "spmm":
                out = ex.spmm(get(op.src[0]), io.mean_w, io)
            elif kind == "add":
                out = get(op.src[0]) + get(op.src[1])
            elif kind == "attn_scores_softmax":
                out = ex.attn_scores_softmax(get(op.src[0]),
                                             get(op.src[1]), io, heads)
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
        env[op.out] = out
    return env[layer.out]


def run_model(ex, spec: ModelSpec, ios: Sequence, X):
    """Full forward pass over ``ios = ex.bind(layer_graphs, spec)``:
    layer l reads/writes the same row set (h_src == h_tgt == H), the
    spec's activation between layers."""
    with obs.span("model.prepare"):
        H = ex.prepare(X)
    L = len(spec.layers)
    for l, layer in enumerate(spec.layers):
        H = run_layer(ex, layer, ios[l], H, H, spec.heads)
        if l < L - 1:
            with obs.span("ops.activation"):
                H = spec.activation(H)
    return H


# ----------------------------------------------------------------------
# RefExecutor — the jnp oracle
# ----------------------------------------------------------------------

class RefExecutor:
    """Single-host pure-jnp backend: the oracle every other executor is
    tested against."""

    name = "ref"

    def bind(self, layer_graphs: Sequence[LayerGraph],
             spec: ModelSpec) -> List[DenseIO]:
        """One ``DenseIO`` per layer of ``spec``, built anew each call."""
        return [DenseIO.from_layer_graph(lg)
                for lg in layer_graphs[:len(spec.layers)]]

    def prepare(self, X):
        return jnp.asarray(X)

    def fetch(self, H) -> np.ndarray:
        """``run_model``'s result on the host: one device-to-host copy."""
        return np.asarray(H)

    def gemm(self, H, W):
        return prim.ref_gemm(H, jnp.asarray(W))

    def spmm(self, H_src, w_edge, io: DenseIO):
        return prim.ref_spmm(H_src, w_edge, io.nbr_resolved, io.mask)

    def attn_scores_softmax(self, q, k, io: DenseIO, heads: int):
        """Per-head scaled dot scores (R, F, h), normalized over each
        row's masked-in slots; k rows may outnumber q rows (universe
        gather)."""
        s = gat_head_scores(q, k, io.nbr_resolved, io.mask, heads)
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

def pad_rows(nbr, mask, *row_arrays):
    """Pad the leading (row) axis of graph-shaped arrays to the next
    multiple of the f32 sublane tile (8) — the ONE pad helper every
    Pallas call site shares; each kernel then grids over its default
    row block, ``auto_block_n`` of the padded count, and its default
    128-lane feature block.  ``nbr`` pads with 0 (a valid in-range id)
    and ``mask`` with False, so padded slots contribute exactly 0.0 and
    the output slice-back is value-neutral.  Returns (nbr, mask,
    *row_arrays), every extra array zero-padded the same way."""
    R = nbr.shape[0]
    Rp = -(-R // 8) * 8

    def pad(a, fill=0):
        if a.shape[0] == Rp:
            return a
        widths = [(0, Rp - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, widths, constant_values=fill)

    return (pad(nbr), pad(mask, fill=False)) + tuple(
        pad(a) for a in row_arrays)


class PallasExecutor(RefExecutor):
    """Routes spmm and the attention through the Pallas kernels
    (``kernels.ops`` dispatch: compiled on TPU, interpret mode
    elsewhere).  GEMM stays on XLA's MXU path — a hand-written matmul
    kernel would only lose.  Rows are padded here (``pad_rows``) and
    feature columns to whole lane tiles inside the kernels, then sliced
    back — non-aligned shapes just work.

    ``use_kernel=False`` runs the jnp oracles in the kernels' place off
    the chip (a test seam; a TPU always runs the kernels).
    """

    name = "pallas"

    def __init__(self, use_kernel: bool = True):
        self.use_kernel = use_kernel

    def spmm(self, H_src, w_edge, io: DenseIO):
        """``gather_spmm`` where the binding has a ``table`` (the id
        translation rides the row gather), ``spmm`` over ``nbr``
        otherwise.  (R, F, heads) weights go head-major."""
        R = io.nbr.shape[0]
        nbr, mask, w_edge = pad_rows(io.nbr, io.mask, w_edge)
        if w_edge.ndim == 3:
            w_edge = w_edge.transpose(2, 0, 1)
        if io.table is not None:
            out = kops.gather_spmm(H_src, io.table, w_edge, nbr, mask,
                                   use_kernel=self.use_kernel)
        else:
            out = kops.spmm(H_src, w_edge, nbr, mask,
                            use_kernel=self.use_kernel)
        return out[:R]

    def attn_scores_softmax(self, q, k, io: DenseIO, heads: int):
        """The ``gat_attention`` kernel: every head's scores and masked
        softmax in one pass, the (R, F) scores never in HBM."""
        R = io.nbr.shape[0]
        nbr, mask, qp = pad_rows(io.nbr_resolved, io.mask, q)
        alpha = kops.gat_attention(qp, k, nbr, mask, heads=heads,
                                   use_kernel=self.use_kernel)
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
    """Deal's distributed backend on a ("data", "model") mesh: the Deal
    GEMM (tiled all-to-alls), the ring SPMM and the per-head attention of
    ``core.primitives``.

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

    def __init__(self, mesh):
        self.mesh = mesh
        self.P = mesh.shape["data"]
        self.M = mesh.shape["model"]
        self._gemm = prim.make_gemm(mesh)
        self._spmm = prim.make_spmm_p(mesh, self.P)
        self._sddmm = prim.make_sddmm_p(mesh, self.P)
        self._attn_cache: Dict[int, Callable] = {}
        self._rows = prim.make_rows(mesh)
        self._row_spec = NamedSharding(mesh, P("data", None))
        self._hd_spec = NamedSharding(mesh, P("data", "model"))
        self._plan_spec = NamedSharding(mesh, P("data", None, None))
        self.plan = None
        self._bound = None           # (layer graphs, DistIOs) of bind

    # -- plumbing -------------------------------------------------------
    def _put(self, x, spec):
        return jax.device_put(x, spec)

    def _attn_fn(self, heads: int) -> Callable:
        if heads not in self._attn_cache:
            self._attn_cache[heads] = prim.make_gat_attention_p(
                self.mesh, self.P, heads)
        obs.gauge("dist.attn_heads_local", heads / self.M)
        return self._attn_cache[heads]

    # -- full-graph binding ---------------------------------------------
    def bind(self, layer_graphs: Sequence[LayerGraph],
             spec: ModelSpec) -> List[DistIO]:
        """One ``DistIO`` per layer of ``spec``.  The CommPlan and its
        device arrays are built on the first call and kept while the
        layer graphs are the same objects (the delta engine resamples
        its own copies)."""
        lgs = list(layer_graphs[:len(spec.layers)])
        bound = self._bound
        if bound is None or len(bound[0]) != len(lgs) or any(
                a is not b for a, b in zip(bound[0], lgs)):
            self._bound = bound = (lgs, self._build_ios(lgs))
        return bound[1]

    def _build_ios(self, layer_graphs: List[LayerGraph]) -> List[DistIO]:
        with obs.span("dist.bind") as bsp:
            self.plan = build_plan(layer_graphs, self.P, self.M)
            ios = []
            for l, lp in enumerate(self.plan.layers):
                lg = layer_graphs[l]
                obs.gauge(f"dist.ring_rows.layer{l}",
                          int(lp.send_count[:, 1:].sum()))
                # the share of the dense slot gather that reads real edges
                obs.gauge(f"dist.slot_fill.layer{l}", float(lg.mask.mean()))
                mask_f = self._put(lg.mask.astype(np.float32),
                                   self._row_spec)
                ios.append(DistIO(
                    args=(mask_f, self._put(lp.send_local, self._plan_spec),
                          self._put(lp.slot_src, self._plan_spec)),
                    mean_w=self._put(mean_weights(lg.mask),
                                     self._row_spec)))
            if bsp:
                bsp.set(n_layers=len(ios), P=self.P, M=self.M)
        return ios

    # -- executor primitives --------------------------------------------
    def prepare(self, X):
        return self._put(X, self._hd_spec)

    def fetch(self, H) -> np.ndarray:
        """``run_model``'s result on the host, copied in whole rows.  The
        primitives leave H in (data, model) column shards, which the
        host would write into a fresh array half-row by half-row (most
        of the copy's time on the chip); ``dist_rows`` first relays H on
        the device into P*M blocks of whole rows, so the host writes P*M
        contiguous blocks."""
        H = self._rows(H)
        obs.add("dist.fetch_relayouts")
        return np.asarray(H)

    def gemm(self, H, W):
        return self._gemm(H, jnp.asarray(W))

    def spmm(self, H_src, w_edge, io: DistIO):
        return self._spmm(H_src, w_edge, *io.args)

    def attn_scores_softmax(self, q, k, io: DistIO, heads: int):
        """Per-head scores and edge softmax: one shard_map for heads > 1;
        for one head, the full-width dot (``dist_sddmm``) / sqrt(D), then
        the softmax."""
        if heads == 1:
            scores = self._sddmm(q, k, *io.args)
            D = q.shape[1]                   # full width (global array)
            return edge_softmax(scores / np.sqrt(D), io.mask_f > 0)
        return self._attn_fn(heads)(q, k, *io.args)

    def attend(self, alpha, v, io: DistIO, heads: int):
        """The ring SPMM, each exchanged row weighted per head: (N, F,
        heads) weights go head-major to the model shards that hold each
        head's lanes (repeated where a head spans shards)."""
        if alpha.ndim == 3 and alpha.shape[-1] < self.M:
            alpha = jnp.repeat(alpha, self.M // alpha.shape[-1], axis=-1)
        return self._spmm(v, alpha, *io.args)

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
        assert self.M & (self.M - 1) == 0, \
            "model axis must be a power of two (pad buckets)"
        with obs.span("dist.subset_plan") as psp:
            sp = build_subset_plan_cached(lg, rows, self.P,
                                          m_align=self.M,
                                          floor=SUBSET_FLOOR,
                                          n_nodes=n_nodes)
            if psp:
                psp.set(rows=int(rows.size), src_rows=int(sp.n_src_rows),
                        level=level)
        mask = sp.row_mask.reshape(-1, sp.fanout)
        mask_f = self._put(mask.astype(np.float32), self._row_spec)
        args = (mask_f, jnp.asarray(sp.send_local), jnp.asarray(sp.slot_src))
        io = DistIO(args=args,
                    mean_w=self._put(mean_weights(mask), self._row_spec))
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
