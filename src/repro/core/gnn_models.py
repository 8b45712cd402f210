"""GNN models: parameter initializers and the DECLARATIVE layer specs.

The paper evaluates 3-layer GCN and GAT (4 heads).  Our GAT uses dot-product
attention (q.k per sampled edge) so that edge scoring exercises the SDDMM
primitive exactly as §3.4 describes; classic additive GAT decomposes into
node terms and would never need SDDMM.  Heads are laid out head-major in the
feature dim, so the distributed engine gives each `model` shard whole heads
(heads % M == 0) or a whole share of one (M % heads == 0).

Each model's per-layer math is defined ONCE, as a sequence of declarative
layer ops (gemm / spmm / attn_scores_softmax / attend / add) over
two input slots — ``h_tgt`` (rows being produced) and ``h_src`` (rows
being aggregated from; identical to ``h_tgt`` in full-graph inference,
the gathered universe in row-subset delta refresh).  ``core.ops``
interprets the spec against one of the interchangeable executors
(ref / pallas / dist), so no engine reimplements the layer math.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.registry import MODELS, register_model
from repro.kernels.ref import F32_MATMUL


def init_gcn(rng, dims: List[int]) -> Dict[str, Any]:
    ks = jax.random.split(rng, len(dims) - 1)
    return {"w": [jax.random.normal(k, (dims[i], dims[i + 1]),
                                    jnp.float32) * (dims[i] ** -0.5)
                  for i, k in enumerate(ks)]}


def init_gat(rng, dims: List[int], heads: int = 4) -> Dict[str, Any]:
    layers = []
    for i in range(len(dims) - 1):
        k = jax.random.fold_in(rng, i)
        kq, kk, kv = jax.random.split(k, 3)
        s = dims[i] ** -0.5
        layers.append({
            "wq": jax.random.normal(kq, (dims[i], dims[i + 1]), jnp.float32) * s,
            "wk": jax.random.normal(kk, (dims[i], dims[i + 1]), jnp.float32) * s,
            "wv": jax.random.normal(kv, (dims[i], dims[i + 1]), jnp.float32) * s,
        })
    return {"layers": layers, "heads": heads}


def init_sage(rng, dims: List[int]) -> Dict[str, Any]:
    layers = []
    for i in range(len(dims) - 1):
        k = jax.random.fold_in(rng, i)
        k1, k2 = jax.random.split(k)
        s = dims[i] ** -0.5
        layers.append({
            "w_self": jax.random.normal(k1, (dims[i], dims[i + 1]),
                                        jnp.float32) * s,
            "w_nbr": jax.random.normal(k2, (dims[i], dims[i + 1]),
                                       jnp.float32) * s,
        })
    return {"layers": layers}


def mean_weights(mask: np.ndarray) -> np.ndarray:
    """Mean-aggregation edge weights from a fanout mask."""
    deg = np.maximum(mask.sum(axis=1, keepdims=True), 1)
    return (mask / deg).astype(np.float32)


def masked_softmax(scores: jax.Array, mask: jax.Array) -> jax.Array:
    s = jnp.where(mask, scores, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return p * mask


def edge_softmax(scores: jax.Array, mask: jax.Array) -> jax.Array:
    """``masked_softmax`` over the fanout axis of (N, F) or per-head
    (N, F, heads) edge scores; ``mask`` is (N, F)."""
    if scores.ndim == 2:
        return masked_softmax(scores, mask)
    return masked_softmax(scores.transpose(0, 2, 1),
                          mask[:, None, :]).transpose(0, 2, 1)


def gat_head_scores(q, kf, nbr, mask, heads: int):
    """Per-head dot scores (N, F, h) from full-width q/k (single host).
    kf rows may outnumber q rows (row-subset universe gather)."""
    N, D = q.shape
    dh = D // heads
    qh = q.reshape(N, heads, dh)
    kh = kf.reshape(-1, heads, dh)
    kn = jnp.take(kh, nbr.reshape(-1), axis=0).reshape(
        nbr.shape + (heads, dh))
    s = jnp.einsum("nhd,nfhd->nfh", qh, kn, precision=F32_MATMUL) / jnp.sqrt(
        jnp.float32(dh))
    return s


# ----------------------------------------------------------------------
# declarative layer specs (executed by core.ops — see module docstring)
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerOp:
    """One declarative op inside a layer program.

    kind     gemm | spmm | add | attn_scores_softmax | attend
    out      env slot written
    src      env slots read ("h_tgt"/"h_src" are the layer inputs)
    param    weight matrix (gemm only)
    """
    kind: str
    out: str
    src: Tuple[str, ...] = ()
    param: Any = None


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    ops: Tuple[LayerOp, ...]
    out: str = "h"


@dataclasses.dataclass
class ModelSpec:
    """One model == a sequence of LayerSpecs + head count + activation
    (applied between layers, not after the last)."""
    model: str
    layers: List[LayerSpec]
    heads: int
    activation: Callable


@dataclasses.dataclass(frozen=True)
class ModelPlugin:
    """A registered GNN model: ``init(key, dims, heads) -> params`` and
    ``spec(params) -> ModelSpec`` (the declarative layer program every
    executor interprets).  Third-party models register one of these
    under a new name (``api.registry.register_model``) and become legal
    ``DealConfig.model.name`` values everywhere — engines, delta
    refresh, serving — with zero core edits."""
    init: Callable
    spec: Callable


def _gcn_spec(params: Dict[str, Any]) -> ModelSpec:
    layers = [LayerSpec(ops=(
        LayerOp("gemm", "hw", ("h_src",), w),
        LayerOp("spmm", "h", ("hw",)),
    )) for w in params["w"]]
    return ModelSpec("gcn", layers, heads=1, activation=jax.nn.relu)


def _sage_spec(params: Dict[str, Any]) -> ModelSpec:
    layers = [LayerSpec(ops=(
        LayerOp("spmm", "agg", ("h_src",)),
        LayerOp("gemm", "own", ("h_tgt",), p["w_self"]),
        LayerOp("gemm", "nb", ("agg",), p["w_nbr"]),
        LayerOp("add", "h", ("own", "nb")),
    )) for p in params["layers"]]
    return ModelSpec("sage", layers, heads=1, activation=jax.nn.relu)


def _gat_spec(params: Dict[str, Any]) -> ModelSpec:
    layers = [LayerSpec(ops=(
        LayerOp("gemm", "q", ("h_tgt",), p["wq"]),
        LayerOp("gemm", "k", ("h_src",), p["wk"]),
        LayerOp("gemm", "v", ("h_src",), p["wv"]),
        LayerOp("attn_scores_softmax", "alpha", ("q", "k")),
        LayerOp("attend", "h", ("alpha", "v")),
    )) for p in params["layers"]]
    return ModelSpec("gat", layers, heads=int(params.get("heads", 1)),
                     activation=jax.nn.elu)


register_model("gcn", ModelPlugin(
    init=lambda key, dims, heads=1: init_gcn(key, dims), spec=_gcn_spec))
register_model("sage", ModelPlugin(
    init=lambda key, dims, heads=1: init_sage(key, dims), spec=_sage_spec))
register_model("gat", ModelPlugin(
    init=lambda key, dims, heads=1: init_gat(key, dims, heads=heads),
    spec=_gat_spec))


def model_spec(model: str, params: Dict[str, Any]) -> ModelSpec:
    """The single definition of each model's layer math, as data —
    resolved through the model registry so registered third-party
    models work everywhere the built-ins do."""
    try:
        plugin = MODELS.get(model)
    except KeyError as exc:
        raise ValueError(str(exc)) from None
    return plugin.spec(params)
