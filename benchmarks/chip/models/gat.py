"""GAT, the paper's dot-product graph attention (rows head-major, heads
of dh = d / heads lanes):

  q, k, v = h Wq, h Wk, h Wv;
  a[i,f,h] = softmax_f over masked-in slots of
             <q_h[i], k_h[nbr[i,f]]> / sqrt(dh);
  h'_h[i] = sum_f a[i,f,h] v_h[nbr[i,f]], elu between layers

Params as the program keeps them: ``{"layers": [{"wq", "wk", "wv"}, ...],
"heads": heads}``, each weight (d, d).  Per layer the epoch runs three
GEMMs, ``gat_attention`` (scores and edge softmax) and the attend.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import reference as ref
import work

GATHERED = (False, True, True)  # q at the block's own rows; k, v at ids


def make_inputs(seed: int, n: int, model: Dict) -> Tuple[np.ndarray, Dict]:
    layers = model["n_layers"]
    X, W = ref.draw(seed, n=n, d=model["d_feature"], layers=layers, per=3)
    return X, {"layers": [{"wq": W[l, 0], "wk": W[l, 1], "wv": W[l, 2]}
                          for l in range(layers)],
               "heads": model["heads"]}


def layer(params: Dict, l: int) -> Dict:
    return dict(params["layers"][l], heads=int(params["heads"]))


def operands(h, p: Dict, matmul: str):
    return tuple(ref.dot(h, p[w], matmul) for w in ("wq", "wk", "wv"))


@functools.partial(jax.jit, static_argnames=("heads",))
def _attend(q, kn, vn, mask, *, heads: int):
    B, F, D = kn.shape
    dh = D // heads
    kn = kn.reshape(B, F, heads, dh)
    vn = vn.reshape(B, F, heads, dh)
    s = (q.reshape(B, 1, heads, dh) * kn).sum(-1) / jnp.sqrt(
        jnp.float32(dh))                                      # (B, F, h)
    m = mask[:, :, None]
    s = jnp.where(m, s, -jnp.inf)
    smax = jnp.max(s, axis=1, keepdims=True)
    e = jnp.where(m, jnp.exp(s - jnp.where(jnp.isfinite(smax), smax, 0.0)),
                  0.0)
    a = e / jnp.maximum(e.sum(axis=1, keepdims=True), 1e-30)
    return (a[..., None] * vn).sum(axis=1).reshape(B, D)


def block(p: Dict, q, kn, vn, mask, matmul: str):
    return _attend(q, kn, vn, mask, heads=p["heads"])


def activation(x):
    return jnp.where(x > 0, x, jnp.expm1(jnp.minimum(x, 0.0)))


def epoch_calls(graphs: Sequence[work.GraphShape], model: Dict
                ) -> List[Tuple[str, work.Work]]:
    """The attend counts as ``heads`` logical spmm calls of dh lanes
    each, though the program runs it as one call over all heads: the
    count is what ``spmm_roofline`` has always read."""
    d, heads = model["d_feature"], model["heads"]
    calls: List[Tuple[str, work.Work]] = []
    for g in graphs:
        calls += [("gemm", work.gemm(g.n, d, d))] * 3
        calls.append(("gat_attention", work.gat_attention(g, d, heads)))
        calls += [("spmm", work.spmm(g, d // heads))] * heads
    return calls


def epoch_min_bytes(graphs: Sequence[work.GraphShape], model: Dict) -> float:
    return work.fused_epoch_bytes(graphs, model["d_feature"], weights=3)
