"""The ego-network BASELINE of the Fig 14 comparison (DGI/SALIENT++-style
batched inference).

Deal's own layer-by-layer all-node pass is ``core.ops.run_model`` over
``ex.bind(layer_graphs, spec)``, for every executor.  The baseline here
computes identical math on the same sampled layer graphs, but batch by
batch over multi-hop dependency frontiers, so cross-batch redundancy
costs real work — exactly the waste DEAL removes.  It runs through the
same executor primitives, so it too can retarget backends.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gnn_models import mean_weights
from repro.core.ops import DenseIO, get_executor
from repro.core.sampler import LayerGraph


def ego_batched_gcn_infer(layer_graphs: List[LayerGraph], X, params,
                          batch_size: int, activation=jax.nn.relu,
                          executor="ref"):
    """Identical outputs to Deal's GCN epoch (``run_model``), computed
    per target batch over multi-hop frontiers; work scales with the
    summed frontier sizes."""
    ex = get_executor(executor)
    X = jnp.asarray(X)
    N = layer_graphs[0].n_nodes
    L = len(params["w"])
    out = np.zeros((N, params["w"][-1].shape[1]), np.float32)
    work_rows = 0
    for b0 in range(0, N, batch_size):
        targets = np.arange(b0, min(b0 + batch_size, N))
        # dependency frontiers: needed[l] = inputs of layer l
        needed = [None] * (L + 1)
        needed[L] = targets
        for l in range(L - 1, -1, -1):
            lg = layer_graphs[l]
            up = needed[l + 1]
            nbrs = lg.nbr[up][lg.mask[up]]
            needed[l] = np.unique(np.concatenate([up, nbrs]))
        H = X[jnp.asarray(needed[0])]
        cur = needed[0]
        for l, w in enumerate(params["w"]):
            lg = layer_graphs[l]
            nxt = needed[l + 1]
            work_rows += cur.size
            Hw = ex.gemm(H, w)
            # remap the layer graph of `nxt` onto positions in `cur`
            pos = np.searchsorted(cur, lg.nbr[nxt])
            pos = np.clip(pos, 0, cur.size - 1)
            valid = lg.mask[nxt] & (cur[pos] == lg.nbr[nxt])
            wts = jnp.asarray(mean_weights(lg.mask[nxt]) * valid)
            H = ex.spmm(Hw, wts, DenseIO(pos, valid))
            if l < L - 1:
                H = activation(H)
            cur = nxt
        out[targets] = np.asarray(H[np.searchsorted(needed[L], targets)])
    return jnp.asarray(out), work_rows
