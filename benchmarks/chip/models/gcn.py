"""GCN, the paper's 3-layer graph convolution with mean aggregation:

  h'[i] = sum_f m[i,f] / max(sum_f m[i,f], 1) * (h W)[nbr[i,f]],
  relu between layers

Params as the program keeps them: ``{"w": [W_1, ..., W_L]}``, each
(d, d).  Per layer the epoch runs one GEMM and one ``spmm``.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import reference as ref
import work

GATHERED = (True,)          # (h W) is read at the neighbour ids


def make_inputs(seed: int, n: int, model: Dict) -> Tuple[np.ndarray, Dict]:
    layers = model["n_layers"]
    X, W = ref.draw(seed, n=n, d=model["d_feature"], layers=layers, per=1)
    return X, {"w": [W[l, 0] for l in range(layers)]}


def layer(params: Dict, l: int):
    return params["w"][l]


def operands(h, w, matmul: str):
    return (ref.dot(h, w, matmul),)


@jax.jit
def _mean(hwn, mask):
    m = mask.astype(jnp.float32)
    w = m / jnp.maximum(m.sum(axis=1, keepdims=True), 1.0)
    return (w[..., None] * hwn).sum(axis=1)


def block(w, hwn, mask, matmul: str):
    return _mean(hwn, mask)


activation = jax.nn.relu


def epoch_calls(graphs: Sequence[work.GraphShape], model: Dict
                ) -> List[Tuple[str, work.Work]]:
    d = model["d_feature"]
    calls: List[Tuple[str, work.Work]] = []
    for g in graphs:
        calls += [("gemm", work.gemm(g.n, d, d)), ("spmm", work.spmm(g, d))]
    return calls


def epoch_min_bytes(graphs: Sequence[work.GraphShape], model: Dict) -> float:
    return work.fused_epoch_bytes(graphs, model["d_feature"], weights=1)
