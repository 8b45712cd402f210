"""Quickstart: DEAL's layer-wise all-node GNN inference in ~40 lines.

  PYTHONPATH=src python examples/quickstart.py
"""
import sys
sys.path.insert(0, "src")

import jax
import numpy as np

from repro.core.gnn_models import init_gcn, model_spec
from repro.core.graph import csr_from_edges, rmat_edges
from repro.core.ops import get_executor, run_model
from repro.core.sampler import sample_layer_graphs
from repro.kernels import ops

# 1. a graph (edge list -> CSR, the paper's stage 1)
src, dst = rmat_edges(n_nodes=1024, n_edges=16_384, seed=0)
g = csr_from_edges(src, dst, 1024)
print(f"graph: {g.n_nodes} nodes, {g.n_edges} edges")

# 2. layer-wise 1-hop sampling: k independent layer graphs for ALL nodes
#    (DEAL's key idea — no multi-hop ego networks, 100% sharing)
lgs = sample_layer_graphs(g, fanout=8, n_layers=3, seed=0)
print(f"sampled {len(lgs)} layer graphs, fanout {lgs[0].fanout}")

# 3. a 3-layer GCN, inferred for every node in one layer-by-layer pass
X = np.random.default_rng(0).standard_normal((1024, 64), dtype=np.float32)
params = init_gcn(jax.random.PRNGKey(0), [64, 64, 64, 32])
ex, spec = get_executor("ref"), model_spec("gcn", params)
H = run_model(ex, spec, ex.bind(lgs, spec), X)
print(f"embeddings for all nodes: {H.shape}, finite={bool(np.isfinite(np.asarray(H)).all())}")

# 4. the Pallas SPMM kernel (TPU target, interpret-validated on CPU)
import jax.numpy as jnp
from repro.core.gnn_models import mean_weights
out = ops.spmm(jnp.asarray(X), jnp.asarray(mean_weights(lgs[0].mask)),
               jnp.asarray(lgs[0].nbr), jnp.asarray(lgs[0].mask),
               use_kernel=True, block_n=8, block_d=64)
ref = ops.spmm(jnp.asarray(X), jnp.asarray(mean_weights(lgs[0].mask)),
               jnp.asarray(lgs[0].nbr), jnp.asarray(lgs[0].mask))
print("pallas spmm max err vs oracle:",
      float(jnp.abs(out - ref).max()))
