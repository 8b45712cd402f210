"""Figs 16-19 in ONE subprocess (8 host devices): distributed GEMM
(DEAL vs CAGNET), SPMM (feature- vs graph-exchange), SDDMM (approach i vs
ii over (P, M) grids), and the ring row exchange vs a monolithic
all-gather."""
from benchmarks.common import run_dist_script

_SCRIPT = r"""
SMOKE = @SMOKE@
import numpy as np, jax, jax.numpy as jnp, time
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import primitives as prim
from repro.core.graph import csr_from_edges, make_dataset, truncate_to_multiple
from repro.core.gnn_models import mean_weights
from repro.core.partition import build_plan, comm_volume
from repro.core.sampler import sample_layer_graphs
from repro.launch.mesh import make_host_mesh

def tmed(fn, *a, iters=3):
    jax.block_until_ready(fn(*a))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter(); jax.block_until_ready(fn(*a))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts)//2]

rng = np.random.default_rng(0)

# ---------------- Fig 16: GEMM ----------------
for D in (256,) if SMOKE else (256, 1024):
    mesh = make_host_mesh(4, 2)
    N = 512 if SMOKE else 8192
    H = jax.device_put(jnp.asarray(rng.standard_normal((N, D), dtype=np.float32)),
                       NamedSharding(mesh, P("data", "model")))
    W = jnp.asarray(rng.standard_normal((D, D), dtype=np.float32))
    td = tmed(prim.make_gemm(mesh, "deal"), H, W)
    tr = tmed(prim.make_gemm(mesh, "deal_ring"), H, W)
    tc = tmed(prim.make_gemm(mesh, "cagnet"), H, W)
    print(f"CSV,fig16/gemm_d{D}/deal,{td*1e6:.1f},speedup_vs_cagnet={tc/td:.2f}x")
    print(f"CSV,fig16/gemm_d{D}/deal_ring,{tr*1e6:.1f},speedup_vs_cagnet={tc/tr:.2f}x")
    print(f"CSV,fig16/gemm_d{D}/cagnet,{tc*1e6:.1f},")

# shared graph setup for sparse primitives
datasets = {}
for name in ("social-spammer",) if SMOKE else (
        "ogbn-products", "social-spammer", "ogbn-papers100M"):
    src, dst, n = make_dataset(name, scale=0.05 if SMOKE else 0.25)
    src, dst, n = truncate_to_multiple(src, dst, n, 8)
    g = csr_from_edges(src, dst, n)
    lgs = sample_layer_graphs(g, fanout=8, n_layers=1, seed=0)
    datasets[name] = (g, lgs)

D = 128
# ---------------- Fig 17: SPMM ----------------
mesh = make_host_mesh(4, 2)
for name, (g, lgs) in datasets.items():
    n = g.n_nodes
    plan = build_plan(lgs, 4, 2)
    lp = plan.layers[0]; dev = prim.plan_device_arrays(lp)
    H = jax.device_put(jnp.asarray(rng.standard_normal((n, D), dtype=np.float32)),
                       NamedSharding(mesh, P("data", "model")))
    w = jax.device_put(jnp.asarray(mean_weights(lgs[0].mask)),
                       NamedSharding(mesh, P("data", None)))
    mask_f = jax.device_put(jnp.asarray(lgs[0].mask, jnp.float32),
                            NamedSharding(mesh, P("data", None)))
    deal_args = (mask_f, dev["send_local"], dev["slot_src"])
    tf = tmed(prim.make_spmm(mesh, lp, "deal"), H, w, *deal_args)
    tg = tmed(prim.make_spmm(mesh, lp, "graph_exchange"), H, w,
              dev["mirror_src"], dev["edge_dst"], dev["edge_slot"], dev["edge_mask"])
    vol = comm_volume(plan, D)["layer0"]
    print(f"CSV,fig17/spmm/{name}/feature_exchange,{tf*1e6:.1f},speedup={tg/tf:.2f}x;bytes={vol['deal_feature_exchange_B']}")
    print(f"CSV,fig17/spmm/{name}/graph_exchange,{tg*1e6:.1f},bytes={vol['graph_exchange_B']}")

# ---------------- Fig 18: SDDMM over (P, M) ----------------
name = "social-spammer"
g, lgs = datasets[name]
n = g.n_nodes
for (Pg, M) in ((4, 2),) if SMOKE else ((1, 8), (2, 4), (4, 2), (8, 1)):
    mesh = make_host_mesh(Pg, M)
    plan = build_plan(lgs, Pg, M)
    lp = plan.layers[0]; dev = prim.plan_device_arrays(lp)
    sh = NamedSharding(mesh, P("data", "model"))
    q = jax.device_put(jnp.asarray(rng.standard_normal((n, D), dtype=np.float32)), sh)
    k = jax.device_put(jnp.asarray(rng.standard_normal((n, D), dtype=np.float32)), sh)
    mask_f = jax.device_put(jnp.asarray(lgs[0].mask, jnp.float32),
                            NamedSharding(mesh, P("data", None)))
    args = (mask_f, dev["send_local"], dev["slot_src"])
    tii = tmed(prim.make_sddmm(mesh, lp, "deal"), q, k, *args)
    ti = tmed(prim.make_sddmm(mesh, lp, "dup"), q, k, *args)
    print(f"CSV,fig18/sddmm/p{Pg}m{M}/split,{tii*1e6:.1f},speedup_vs_dup={ti/tii:.2f}x")
    print(f"CSV,fig18/sddmm/p{Pg}m{M}/dup,{ti*1e6:.1f},")

# ---------------- Fig 19: ring exchange vs monolithic all-gather ----------------
mesh = make_host_mesh(4, 2)
for name, (g, lgs) in datasets.items():
    n = g.n_nodes
    plan = build_plan(lgs, 4, 2)
    lp = plan.layers[0]; dev = prim.plan_device_arrays(lp)
    H = jax.device_put(jnp.asarray(rng.standard_normal((n, D), dtype=np.float32)),
                       NamedSharding(mesh, P("data", "model")))
    w = jax.device_put(jnp.asarray(mean_weights(lgs[0].mask)),
                       NamedSharding(mesh, P("data", None)))
    args = (jax.device_put(jnp.asarray(lgs[0].mask, jnp.float32),
                           NamedSharding(mesh, P("data", None))),
            dev["send_local"], dev["slot_src"])
    nbr = jnp.asarray(lgs[0].nbr.reshape(4, n//4, -1))
    msk = jnp.asarray(lgs[0].mask.reshape(4, n//4, -1))
    t_mono = tmed(prim.make_spmm(mesh, lp, "allgather"), H, w, nbr, msk)
    t_deal = tmed(prim.make_spmm(mesh, lp, "deal"), H, w, *args)
    # network bytes per device (what a real 25Gbps/ICI fabric pays):
    deal_B = comm_volume(plan, D)["layer0"]["deal_feature_exchange_B"] / 4
    ag_B = (4 - 1) / 4 * n * (D // 2) * 4        # all-gather of the tile
    print(f"CSV,fig19/spmm/{name}/deal_ring,{t_deal*1e6:.1f},host_speedup_vs_allgather={t_mono/t_deal:.2f}x;net_bytes_ratio={ag_B/max(deal_B,1):.1f}x")
    print(f"CSV,fig19/spmm/{name}/allgather_monolithic,{t_mono*1e6:.1f},net_bytes={ag_B:.0f}")
"""


def run(smoke: bool = False):
    run_dist_script(_SCRIPT, smoke)
