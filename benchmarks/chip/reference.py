"""The plain reference the benchmark holds the program to.

Everything here and under ``models/`` is written from the models'
equations and imports nothing of the program: the inputs (features,
weights) come from the model file's ``make_inputs``, drawn from the
run's seed through ``draw`` in one jitted call on the device; the
sampled layer graphs are the run's input data, read from the program
but first checked edge by edge against the edge list
(``graph_violations``).

A model is the file ``models/<name>.py`` (``bench.load_model``), which
gives its equations to ``forward`` here:

  GATHERED    one flag per operand: True where a block reads the
              operand's rows at its neighbour ids, False where it reads
              its own rows
  layer(params, l)                 the weights one layer reads
  operands(h, p, matmul)           a layer's operands from rows of H
  block(p, *operand_blocks, mask, matmul)
                                   a block of output rows
  activation(x)                    between layers, not after the last

The matmuls run in float32 at HIGHEST precision, as the configurations
state.  ``matmul="bf16x3"`` is the control: the same reference with
every matmul in three bfloat16 passes (the TPU's ``high`` precision,
written out so it reads the same on any backend).  The per-edge dots
and sums are elementwise float32 in both.
"""
from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 65536
GATHER_THREADS = 4


# ----------------------------------------------------------------------
# inputs from the seed
# ----------------------------------------------------------------------

def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (``PRNGKey`` keeps 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnames=("n", "d", "layers", "per"))
def _draw(key, *, n: int, d: int, layers: int, per: int):
    kx, kw = jax.random.split(key)
    X = jax.random.normal(kx, (n, d), jnp.float32)
    W = jax.random.normal(kw, (layers, per, d, d), jnp.float32) * d ** -0.5
    return X, W


def draw(seed: int, *, n: int, d: int, layers: int, per: int
         ) -> Tuple[np.ndarray, jax.Array]:
    """Features (n, d) on the host and ``per`` (d, d) weights for each
    of ``layers`` layers, W[l, i], on the device, all from ``seed`` in
    one jitted call."""
    X, W = _draw(seed_key(seed), n=n, d=d, layers=layers, per=per)
    return np.asarray(X), W


# ----------------------------------------------------------------------
# the forward pass
# ----------------------------------------------------------------------

def dot(a, b, matmul: str):
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


def _blocks(n: int, block: int):
    for lo in range(0, n, block):
        yield lo, min(lo + block, n)


def _pad_rows(a: np.ndarray, rows: int) -> np.ndarray:
    if a.shape[0] == rows:
        return a
    out = np.zeros((rows,) + a.shape[1:], a.dtype)
    out[:a.shape[0]] = a
    return out


def _by_rows(f, H: np.ndarray, block: int) -> List[np.ndarray]:
    """``f`` (rows -> a tuple of row-wise results) over H on the host,
    one block of rows at a time on the device."""
    n = H.shape[0]
    outs = None
    for lo, hi in _blocks(n, block):
        res = f(jnp.asarray(_pad_rows(H[lo:hi], block)))
        if outs is None:
            outs = [np.empty((n,) + r.shape[1:], np.float32) for r in res]
        for o, r in zip(outs, res):
            o[lo:hi] = np.asarray(r)[:hi - lo]
    return outs


def _gather(pool: ThreadPoolExecutor, a: np.ndarray, ids: np.ndarray
            ) -> np.ndarray:
    """a[ids] on the host, its rows split over the pool's threads."""
    out = np.empty(ids.shape + a.shape[1:], a.dtype)
    step = -(-ids.shape[0] // GATHER_THREADS)

    def part(lo):
        out[lo:lo + step] = a[ids[lo:lo + step]]
    for f in [pool.submit(part, lo) for lo in range(0, ids.shape[0], step)]:
        f.result()
    return out


def forward(model, params: Dict, X: np.ndarray,
            graphs: Sequence[Tuple[np.ndarray, np.ndarray]], *,
            matmul: str = "highest", block: int = BLOCK_ROWS
            ) -> List[np.ndarray]:
    """Every level of an all-node epoch of ``model`` (a model file):
    [X, h1, ..., hL] on the host.

    The device holds a few blocks of ``block`` rows, never a whole
    level: each layer's operands are computed block by block into host
    arrays, and each block of target rows gets its neighbours' operand
    rows gathered on the host before it goes to the device.  The next
    block's rows are gathered while the device works on this one.
    Blocks are padded to one shape, so each layer compiles once."""
    n = X.shape[0]
    block = min(block, -(-n // 8) * 8)
    levels = [np.asarray(X, np.float32)]
    L = len(graphs)
    with ThreadPoolExecutor(1) as ahead, \
            ThreadPoolExecutor(GATHER_THREADS) as pool:
        for l, (nbr, mask) in enumerate(graphs):
            p = model.layer(params, l)
            ops = _by_rows(lambda h: model.operands(h, p, matmul),
                           levels[-1], block)

            def rows(lo, hi):
                """A block's operand rows and mask, on the host."""
                nb = _pad_rows(nbr[lo:hi], block)
                return ([_gather(pool, a, nb) if gathered
                         else _pad_rows(a[lo:hi], block)
                         for a, gathered in zip(ops, model.GATHERED)],
                        _pad_rows(mask[lo:hi], block))
            spans = list(_blocks(n, block))
            out = None
            nxt = ahead.submit(rows, *spans[0])
            for i, (lo, hi) in enumerate(spans):
                args, mb = nxt.result()
                if i + 1 < len(spans):
                    nxt = ahead.submit(rows, *spans[i + 1])
                o = model.block(p, *map(jnp.asarray, args), jnp.asarray(mb),
                                matmul)
                if l < L - 1:
                    o = model.activation(o)
                if out is None:
                    out = np.empty((n, o.shape[1]), np.float32)
                out[lo:hi] = np.asarray(o)[:hi - lo]
            del ops
            levels.append(out)
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
