"""Fig 3a/3b: end-to-end stage breakdown and the peak-memory argument for
collaborative (graph + feature) partitioning."""
import numpy as np

from benchmarks.common import emit, time_host
from repro.core.graph import csr_from_edges_distributed, make_dataset
from repro.core.partition import build_plan
from repro.core.sampler import sample_layer_graphs


def run(smoke: bool = False):
    D = 32 if smoke else 128
    for name in ("ogbn-products",) if smoke else ("ogbn-products",
                                                  "social-spammer"):
        src, dst, n = make_dataset(name, scale=0.05 if smoke else 0.5)
        from repro.core.graph import truncate_to_multiple
        src, dst, n = truncate_to_multiple(src, dst, n, 8)
        t_con, (g, _) = time_host(
            lambda: csr_from_edges_distributed(src, dst, n, n_workers=4),
            iters=1)
        t_sam, lgs = time_host(
            lambda: sample_layer_graphs(g, fanout=8, n_layers=3, seed=0),
            iters=1)
        t_par, plan = time_host(lambda: build_plan(lgs, 4, 2), iters=1)
        from repro.core.gnn_models import init_gcn, model_spec
        from repro.core.ops import RefExecutor, run_model
        import jax
        X = np.random.default_rng(0).standard_normal((n, D),
                                                     dtype=np.float32)
        params = init_gcn(jax.random.PRNGKey(0), [D, D, D, D])
        ex, spec = RefExecutor(), model_spec("gcn", params)
        t_inf, _ = time_host(
            lambda: np.asarray(run_model(ex, spec, ex.bind(lgs, spec), X)),
            iters=1)
        total = t_con + t_sam + t_par + t_inf
        emit(f"fig3a/breakdown/{name}", total * 1e6,
             f"construct={t_con/total:.0%};sample={t_sam/total:.0%};"
             f"partition={t_par/total:.0%};inference={t_inf/total:.0%}")

        # Fig 3b: per-device peak feature bytes
        P_, M_ = 4, 2
        graph_only = n * D * 4            # all-gathered rows, full width
        lp = plan.layers[0]
        collab = (n // P_ + lp.max_request * (P_ - 1)) * (D // M_) * 4
        emit(f"fig3b/peak_memory/{name}", 0.0,
             f"graph_only_B={graph_only};collaborative_B={collab};"
             f"ratio={graph_only/collab:.1f}x")
