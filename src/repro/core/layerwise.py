"""The DEAL engine: layer-by-layer all-node inference (§3.2, Fig 4).

All engines are thin drivers over the pluggable executor layer
(``core.ops``): each model's layer math is declared once in
``gnn_models.model_spec`` and interpreted against a backend —

  * ``local_*`` — single-host engines (oracle + CPU benchmarks); take an
    ``executor`` argument ("ref" default, "pallas" for the kernels in
    ``kernels/``);
  * ``DistributedLayerwise`` — ``DistExecutor`` on a ("data", "model")
    mesh using the §3.4 primitives and the static CommPlan.

Plus the ego-network BASELINE (DGI/SALIENT++-style batched inference) used
by the Fig 14 comparison: identical math on the same sampled layer graphs,
but computed batch-by-batch over multi-hop dependency frontiers, so
cross-batch redundancy costs real work — exactly the waste DEAL removes.
The baseline runs through the same executor primitives, so it too can
retarget backends.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gnn_models import mean_weights, model_spec
from repro.core.ops import DenseIO, DistExecutor, get_executor, run_model
from repro.core.sampler import LayerGraph


# ----------------------------------------------------------------------
# single-host engines
# ----------------------------------------------------------------------

def _local_infer(model: str, layer_graphs: List[LayerGraph], X, params,
                 activation=None, executor="ref"):
    ex = get_executor(executor)
    spec = model_spec(model, params)
    ios = [DenseIO.from_layer_graph(lg)
           for lg in layer_graphs[:len(spec.layers)]]
    return run_model(ex, spec, ios, X, activation=activation)


def local_gcn_infer(layer_graphs, X, params, activation=jax.nn.relu,
                    executor="ref"):
    return _local_infer("gcn", layer_graphs, X, params, activation,
                        executor)


def local_gat_infer(layer_graphs, X, params, activation=jax.nn.elu,
                    executor="ref"):
    return _local_infer("gat", layer_graphs, X, params, activation,
                        executor)


def local_sage_infer(layer_graphs, X, params, activation=jax.nn.relu,
                     executor="ref"):
    return _local_infer("sage", layer_graphs, X, params, activation,
                        executor)


LOCAL_ENGINES = {"gcn": local_gcn_infer, "gat": local_gat_infer,
                 "sage": local_sage_infer}


# ----------------------------------------------------------------------
# ego-network batched baseline (the DGI/SALIENT++-style computation)
# ----------------------------------------------------------------------

def ego_batched_gcn_infer(layer_graphs: List[LayerGraph], X, params,
                          batch_size: int, activation=jax.nn.relu,
                          executor="ref"):
    """Identical outputs to local_gcn_infer, computed per target batch over
    multi-hop frontiers; work scales with the summed frontier sizes."""
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


# ----------------------------------------------------------------------
# distributed engine
# ----------------------------------------------------------------------

class DistributedLayerwise:
    """DEAL distributed inference: a thin driver binding the model spec
    to a ``DistExecutor`` on a ("data", "model") mesh."""

    def __init__(self, mesh, layer_graphs: List[LayerGraph], model: str,
                 params, *, spmm_variant: str = "deal",
                 gemm_variant: str = "deal", sddmm_variant: str = "deal"):
        self.mesh = mesh
        self.model = model
        self.params = params
        self.layer_graphs = layer_graphs
        self.ex = DistExecutor(mesh, spmm_variant=spmm_variant,
                               gemm_variant=gemm_variant,
                               sddmm_variant=sddmm_variant)
        self.P = self.ex.P
        self.M = self.ex.M
        self.spec = model_spec(model, params)
        self.ios = self.ex.bind(layer_graphs[:len(self.spec.layers)],
                                need_sddmm=(model == "gat"))
        self.plan = self.ex.plan

    def infer(self, X) -> jax.Array:
        return run_model(self.ex, self.spec, self.ios, X)
