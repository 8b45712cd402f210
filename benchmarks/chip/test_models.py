"""The model files the reference is built from (run by path, on the CPU:
``python -m pytest -q benchmarks/chip/test_models.py``).

* ``make_inputs`` draws the same features and weights for a seed as the
  harness always has: digests recorded before the draw moved into the
  model files, for a seed below 2**32 and one above.
* The reference in blocks of rows, with neighbour rows gathered on the
  host, equals bit for bit the same equations over whole arrays on the
  device, written out here, at a size where the blocks do not divide
  the rows, in the configurations' precision and in the control's.
"""
from __future__ import annotations

import functools
import hashlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench  # noqa: E402
import reference as ref  # noqa: E402

TINY = {"gcn": {"name": "gcn", "n_layers": 3, "d_feature": 128, "heads": 1},
        "gat": {"name": "gat", "n_layers": 3, "d_feature": 128, "heads": 4}}
DIGESTS = {   # sha256 of X, then each weight in the params' leaf order
    ("gcn", 7):
        "141c0d1cc07140268c0ac8ecbcad4b4b405f50c0d24ebbe1c834ab999cd999b4",
    ("gcn", 2**32 + 123456789):
        "53f9a670da933d21ab668998f3c2d0f995081ff600453379be0b1aeddc346ea3",
    ("gat", 7):
        "63f8ee2b5ea5a2ef2143bd40c3edb2c8f56678db9224fd3270c10495ab35eea3",
    ("gat", 2**32 + 123456789):
        "201742201fc444ca5360852be5b8aa4e68006594ff880bd0d8a12281d00a059c",
}


def model_file(name):
    return bench._load_module(HERE / "models" / f"{name}.py",
                              "model_" + name)


@pytest.mark.parametrize("name,seed", sorted(DIGESTS))
def test_inputs_are_drawn_as_before(name, seed):
    X, params = model_file(name).make_inputs(seed, 64, TINY[name])
    h = hashlib.sha256(np.ascontiguousarray(X).tobytes())
    for leaf in jax.tree_util.tree_leaves(params):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    assert h.hexdigest() == DIGESTS[name, seed]


# ----------------------------------------------------------------------
# whole-array forms of the models' equations
# ----------------------------------------------------------------------

@jax.jit
def _gcn_layer(hw, nbr, mask):
    m = mask.astype(jnp.float32)
    w = m / jnp.maximum(m.sum(axis=1, keepdims=True), 1.0)
    return (w[..., None] * hw[nbr]).sum(axis=1)


def whole_gcn(params, X, graphs, matmul):
    H, levels = jnp.asarray(X), [np.asarray(X)]
    for l, (nbr, mask) in enumerate(graphs):
        H = _gcn_layer(ref.dot(H, params["w"][l], matmul), nbr, mask)
        if l < len(graphs) - 1:
            H = jax.nn.relu(H)
        levels.append(np.asarray(H))
    return levels


@functools.partial(jax.jit, static_argnames=("heads",))
def _gat_layer(q, k, v, nbr, mask, *, heads):
    n, F = nbr.shape
    dh = q.shape[1] // heads
    kn = k[nbr].reshape(n, F, heads, dh)
    vn = v[nbr].reshape(n, F, heads, dh)
    s = (q.reshape(n, 1, heads, dh) * kn).sum(-1) / jnp.sqrt(
        jnp.float32(dh))
    m = mask[:, :, None]
    s = jnp.where(m, s, -jnp.inf)
    smax = jnp.max(s, axis=1, keepdims=True)
    e = jnp.where(m, jnp.exp(s - jnp.where(jnp.isfinite(smax), smax, 0.0)),
                  0.0)
    a = e / jnp.maximum(e.sum(axis=1, keepdims=True), 1e-30)
    return (a[..., None] * vn).sum(axis=1).reshape(n, -1)


def whole_gat(params, X, graphs, matmul):
    H, levels = jnp.asarray(X), [np.asarray(X)]
    for l, (nbr, mask) in enumerate(graphs):
        p = params["layers"][l]
        q, k, v = (ref.dot(H, p[w], matmul) for w in ("wq", "wk", "wv"))
        H = _gat_layer(q, k, v, nbr, mask, heads=params["heads"])
        if l < len(graphs) - 1:
            H = jnp.where(H > 0, H, jnp.expm1(jnp.minimum(H, 0.0)))
        levels.append(np.asarray(H))
    return levels


WHOLE = {"gcn": whole_gcn, "gat": whole_gat}


def tiny_graphs(n, fanout, seed):
    """Layer graphs with random ids and 0 to ``fanout`` slots in use per
    row (rows with no edge among them)."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(3):
        nbr = rng.integers(0, n, (n, fanout)).astype(np.int32)
        deg = rng.integers(0, fanout + 1, n)
        out.append((nbr, np.arange(fanout)[None, :] < deg[:, None]))
    return out


@pytest.mark.parametrize("matmul", ["highest", "bf16x3"])
@pytest.mark.parametrize("name", sorted(WHOLE))
def test_blocked_reference_is_the_whole_array_one(name, matmul):
    n, seed = 300, 2**33 + 5
    X, params = model_file(name).make_inputs(seed, n, TINY[name])
    graphs = tiny_graphs(n, 8, seed)
    got = ref.forward(model_file(name), params, X, graphs, matmul=matmul,
                      block=64)
    want = WHOLE[name](params, X, graphs, matmul)
    assert len(got) == len(want) == 4
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
