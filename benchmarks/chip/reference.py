"""The plain reference the benchmark holds the program to.

Everything here is written from the model's equations and imports
nothing of the program: the inputs (features, weights) come from
``make_inputs``, drawn from the run's seed in one jitted call on the
device; the sampled layer graphs are the run's input data, read from the
program but first checked edge by edge against the edge list
(``graph_violations``).

Models (the paper's 3-layer GCN and dot-product GAT; rows head-major):

  gcn   h' = sum_f m[i,f] / max(sum_f m[i,f], 1) * (h W)[nbr[i,f]],
        relu between layers
  gat   q, k, v = h Wq, h Wk, h Wv;
        a[i,f,h] = softmax_f over masked-in slots of
                   <q_h[i], k_h[nbr[i,f]]> / sqrt(d / heads);
        h'_h[i] = sum_f a[i,f,h] v_h[nbr[i,f]], elu between layers

The matmuls run in float32 at HIGHEST precision, as the configurations
state.  ``matmul="bf16x3"`` is the control: the same reference with
every matmul in three bfloat16 passes (the TPU's ``high`` precision,
written out so it reads the same on any backend).  The per-edge dots
and sums are elementwise float32 in both.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 65536


# ----------------------------------------------------------------------
# inputs from the seed
# ----------------------------------------------------------------------

def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (``PRNGKey`` keeps 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnames=("model", "n", "d", "layers"))
def _make(key, *, model: str, n: int, d: int, layers: int):
    kx, kw = jax.random.split(key)
    X = jax.random.normal(kx, (n, d), jnp.float32)
    per = {"gcn": 1, "gat": 3}[model]
    W = jax.random.normal(kw, (layers, per, d, d), jnp.float32) * d ** -0.5
    return X, W


def make_inputs(seed: int, model: str, n: int, d: int, layers: int,
                heads: int) -> Tuple[np.ndarray, Dict]:
    """Features (host, as the program keeps them) and weights (device,
    in the program's parameter layout), all from ``seed``."""
    X, W = _make(seed_key(seed), model=model, n=n, d=d, layers=layers)
    if model == "gcn":
        params = {"w": [W[l, 0] for l in range(layers)]}
    else:
        params = {"layers": [{"wq": W[l, 0], "wk": W[l, 1], "wv": W[l, 2]}
                             for l in range(layers)],
                  "heads": heads}
    return np.asarray(X), params


# ----------------------------------------------------------------------
# the forward pass
# ----------------------------------------------------------------------

def _dot(a, b, matmul: str):
    if matmul == "highest":
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    if matmul != "bf16x3":
        raise ValueError(f"unknown matmul precision {matmul!r}")

    def split(x):
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    (a1, a2), (b1, b2) = split(a), split(b)

    def d(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)
    return d(a1, b1) + (d(a1, b2) + d(a2, b1))


def _elu(x):
    return jnp.where(x > 0, x, jnp.expm1(jnp.minimum(x, 0.0)))


@functools.partial(jax.jit, static_argnames=("heads",))
def _gat_block(q, k, v, nbr, mask, *, heads: int):
    B, F = nbr.shape
    D = q.shape[1]
    dh = D // heads
    kn = k[nbr].reshape(B, F, heads, dh)
    vn = v[nbr].reshape(B, F, heads, dh)
    s = (q.reshape(B, 1, heads, dh) * kn).sum(-1) / jnp.sqrt(
        jnp.float32(dh))                                      # (B, F, h)
    m = mask[:, :, None]
    s = jnp.where(m, s, -jnp.inf)
    smax = jnp.max(s, axis=1, keepdims=True)
    e = jnp.where(m, jnp.exp(s - jnp.where(jnp.isfinite(smax), smax, 0.0)),
                  0.0)
    a = e / jnp.maximum(e.sum(axis=1, keepdims=True), 1e-30)
    return (a[..., None] * vn).sum(axis=1).reshape(B, D)


@jax.jit
def _gcn_block(hw, nbr, mask):
    m = mask.astype(jnp.float32)
    w = m / jnp.maximum(m.sum(axis=1, keepdims=True), 1.0)
    return (w[..., None] * hw[nbr]).sum(axis=1)


def _blocks(n: int, block: int):
    for lo in range(0, n, block):
        yield lo, min(lo + block, n)


def _pad_rows(a: np.ndarray, rows: int) -> np.ndarray:
    if a.shape[0] == rows:
        return a
    out = np.zeros((rows,) + a.shape[1:], a.dtype)
    out[:a.shape[0]] = a
    return out


def forward(model: str, params: Dict, X: np.ndarray,
            graphs: Sequence[Tuple[np.ndarray, np.ndarray]], *,
            matmul: str = "highest", block: int = BLOCK_ROWS
            ) -> List[np.ndarray]:
    """Every level of an all-node epoch: [X, h1, ..., hL] on the host.
    Rows are computed in blocks of ``block`` (padded to one shape, so
    each layer compiles once) to bound the device memory."""
    n = X.shape[0]
    block = min(block, -(-n // 8) * 8)
    levels = [np.asarray(X, np.float32)]
    H = jnp.asarray(levels[0])
    L = len(graphs)
    for l, (nbr, mask) in enumerate(graphs):
        out = np.empty((n, H.shape[1]), np.float32)
        if model == "gcn":
            hw = _dot(H, params["w"][l], matmul)
        else:
            p = params["layers"][l]
            q, k, v = (_dot(H, p[w], matmul) for w in ("wq", "wk", "wv"))
        for lo, hi in _blocks(n, block):
            nb = jnp.asarray(_pad_rows(nbr[lo:hi], block))
            mb = jnp.asarray(_pad_rows(mask[lo:hi], block))
            if model == "gcn":
                o = _gcn_block(hw, nb, mb)
            else:
                qb = jnp.pad(q[lo:hi], ((0, block - (hi - lo)), (0, 0)))
                o = _gat_block(qb, k, v, nb, mb,
                               heads=int(params["heads"]))
            out[lo:hi] = np.asarray(o)[:hi - lo]
        if l < L - 1:
            out = np.asarray((jax.nn.relu if model == "gcn" else _elu)(
                jnp.asarray(out)))
        levels.append(out)
        H = jnp.asarray(out)
    return levels


# ----------------------------------------------------------------------
# the comparisons
# ----------------------------------------------------------------------

def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """max|got - want| / max|want|; inf for a shape mismatch or a
    non-finite value."""
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    scale = float(np.abs(want).max())
    return float(np.abs(got - want).max()) / max(scale, 1e-30)


def graph_violations(nbr: np.ndarray, mask: np.ndarray, src: np.ndarray,
                     dst: np.ndarray) -> int:
    """Rows of a sampled layer graph that break the sampling contract
    against the edge list (row v samples in-edges u -> v): a masked-in
    slot naming a non-edge, or a row whose masked-in count is not
    min(in-degree, fanout)."""
    n, fanout = nbr.shape
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    keys = np.sort(dst * n + src)
    deg = np.bincount(dst, minlength=n)
    bad = mask.sum(axis=1) != np.minimum(deg, fanout)
    rows, cols = np.nonzero(mask)
    q = rows.astype(np.int64) * n + nbr[rows, cols].astype(np.int64)
    pos = np.minimum(np.searchsorted(keys, q), keys.size - 1)
    miss = keys[pos] != q
    bad[rows[miss]] = True
    return int(bad.sum())
