"""Fig 15: weak scaling (graph grows with the mesh) and strong scaling
(fixed graph, growing mesh) of the distributed layer-wise engine."""
from benchmarks.common import run_dist_script

_SCRIPT = r"""
SMOKE = @SMOKE@
import numpy as np, jax, jax.numpy as jnp, time
from repro.core.graph import (csr_from_edges, rmat_edges, make_dataset,
                              truncate_to_multiple)
from repro.core.gnn_models import init_gcn, model_spec
from repro.core.ops import DistExecutor, run_model
from repro.core.sampler import sample_layer_graphs
from repro.launch.mesh import make_host_mesh

def epoch_s(mesh, lgs, X, params):
    # median of 3 warm GCN epochs on the mesh, seconds
    ex, spec = DistExecutor(mesh), model_spec("gcn", params)
    ios = ex.bind(lgs, spec)
    jax.block_until_ready(run_model(ex, spec, ios, X))
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(run_model(ex, spec, ios, X))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[1]

def bench(n, e, Pg, M, seed=0, name=""):
    src, dst = rmat_edges(n, e, seed=seed)
    g = csr_from_edges(src, dst, n)
    lgs = sample_layer_graphs(g, fanout=8, n_layers=3, seed=0)
    mesh = make_host_mesh(Pg, M)
    D = 64
    X = np.random.default_rng(0).standard_normal((n, D), dtype=np.float32)
    params = init_gcn(jax.random.PRNGKey(0), [D, D, D, D])
    t = epoch_s(mesh, lgs, X, params)
    eps = g.n_edges / t / (Pg * M)
    print(f"CSV,fig15/{name},{t*1e6:.1f},edges_per_s_per_dev={eps:.0f};edges={g.n_edges}")

# weak scaling: edges proportional to devices
for Pg in (1, 2) if SMOKE else (1, 2, 4, 8):
    n = (256 if SMOKE else 1024) * Pg
    bench(n, n * 16, Pg, 1, name=f"weak/p{Pg}")

# strong scaling on fixed graphs
for name in ("ogbn-products",) if SMOKE else ("ogbn-products",
                                              "social-spammer"):
    src, dst, n = make_dataset(name, scale=0.05 if SMOKE else 0.25)
    src, dst, n = truncate_to_multiple(src, dst, n, 8)
    g = csr_from_edges(src, dst, n)
    lgs = sample_layer_graphs(g, fanout=8, n_layers=3, seed=0)
    D = 64
    X = np.random.default_rng(0).standard_normal((n, D), dtype=np.float32)
    params = init_gcn(jax.random.PRNGKey(0), [D, D, D, D])
    for Pg in (2,) if SMOKE else (2, 4, 8):
        t = epoch_s(make_host_mesh(Pg, 1), lgs, X, params)
        print(f"CSV,fig15/strong/{name}/p{Pg},{t*1e6:.1f},edges={g.n_edges}")
"""


def run(smoke: bool = False):
    run_dist_script(_SCRIPT, smoke)
