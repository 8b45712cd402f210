"""Table 6: accuracy of DEAL's layer-wise sampled inference vs full-neighbor
and mini-batch style inference, GCN + GAT on a planted-partition task."""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core.gnn_models import init_gat, init_gcn, model_spec
from repro.core.graph import csr_from_edges, planted_partition
from repro.core.ops import RefExecutor, run_model
from repro.core.sampler import sample_layer_graphs
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state


def _engine(model):
    """Deal's all-node forward pass of ``model`` on the jnp oracle."""
    ex = RefExecutor()

    def infer(lgs, X, params):
        spec = model_spec(model, params)
        return run_model(ex, spec, ex.bind(lgs, spec), X)
    return infer


def _accuracy(H, labels, train_mask):
    pred = np.asarray(H).argmax(-1)
    test = ~train_mask
    return float((pred[test] == labels[test]).mean())


def _train(engine, init_fn, lgs_train, X, labels, train_mask, dims,
           steps=60, lr=5e-2):
    params = init_fn(jax.random.PRNGKey(0), dims)
    static = {k: v for k, v in params.items() if not isinstance(v, (list, dict))}
    train_p = {k: v for k, v in params.items() if k not in static}
    opt_cfg = AdamWConfig(lr=lr, warmup_steps=2, total_steps=steps,
                          weight_decay=0.0)
    opt = init_opt_state(train_p, opt_cfg)
    y = jnp.asarray(labels)
    m = jnp.asarray(train_mask)

    def loss_fn(p):
        H = engine(lgs_train, X, {**p, **static})
        logp = jax.nn.log_softmax(H, axis=-1)
        nll = -jnp.take_along_axis(logp, y[:, None], -1)[:, 0]
        return jnp.where(m, nll, 0.0).sum() / m.sum()

    grad = jax.jit(jax.value_and_grad(loss_fn))
    for _ in range(steps):
        l, g = grad(train_p)
        train_p, opt, _ = adamw_update(train_p, g, opt, opt_cfg)
    return {**train_p, **static}, float(l)


def run(smoke: bool = False):
    n, n_comm = (256, 4) if smoke else (1024, 8)
    steps = 5 if smoke else 60
    src, dst, labels = planted_partition(n, n_comm, p_in=0.85, p_out=0.15,
                                         seed=1)
    g = csr_from_edges(src, dst, n)
    rng = np.random.default_rng(0)
    X = (np.eye(n_comm, dtype=np.float32)[labels]
         + 0.8 * rng.standard_normal((n, n_comm)).astype(np.float32))
    train_mask = rng.random(n) < 0.5
    full = sample_layer_graphs(g, fanout=64, n_layers=2, seed=0)  # ~full nbr
    dims = [n_comm, 32, n_comm]

    for model, engine, init_fn in (
            ("gcn", _engine("gcn"), init_gcn),
            ("gat", _engine("gat"), lambda k, d: init_gat(k, d, heads=4))):
        params, loss = _train(engine, init_fn, full, X, labels, train_mask,
                              dims, steps=steps)
        acc_full = _accuracy(engine(full, X, params), labels, train_mask)
        # DEAL: shared sampled 1-hop layer graphs for all nodes
        deal_lgs = sample_layer_graphs(g, fanout=8, n_layers=2, seed=7)
        acc_deal = _accuracy(engine(deal_lgs, X, params), labels,
                             train_mask)
        # mini-batch style: per-batch resampled neighborhoods
        accs = []
        for s in range(1 if smoke else 4):
            lgs_s = sample_layer_graphs(g, fanout=8, n_layers=2,
                                        seed=100 + s)
            accs.append(_accuracy(engine(lgs_s, X, params), labels,
                                  train_mask))
        emit(f"tab6/accuracy/{model}", 0.0,
             f"full={acc_full:.3f};deal={acc_deal:.3f};"
             f"minibatch={np.mean(accs):.3f}+-{np.std(accs):.3f};"
             f"train_loss={loss:.3f}")
