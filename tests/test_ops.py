"""Executor equivalence: one declarative layer spec, interchangeable
backends.

  * RefExecutor must match hand-rolled jnp math (the spec cannot drift);
  * PallasExecutor (interpret mode) must match ref within dtype tolerance
    for every model, on NON-ALIGNED N/D shapes (the executor pads to
    kernel blocks internally), float32 and bfloat16;
  * delta refresh through the pallas executor must stay bitwise-equal to
    a full epoch through the same executor (the dist twin of this check
    lives in tests/helpers/dist_check.py — meshes need a subprocess).
"""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.gnn_models import (init_gat, init_gcn, init_sage,
                                   mean_weights, model_spec)
from repro.core.graph import csr_from_edges, rmat_edges
from repro.core.ops import (DenseIO, PallasExecutor, RefExecutor,
                            get_executor, run_model)
from repro.core.sampler import sample_layer_graphs

N, D = 200, 48              # deliberately non-aligned (not pow2/128-mult)
FANOUT, L = 6, 2
DIMS = [D, 48, 40]          # head-major gat: every width % heads == 0

ATOL = {jnp.float32: 2e-4, jnp.bfloat16: 0.25}


@pytest.fixture(scope="module")
def world():
    src, dst = rmat_edges(N, N * 8, seed=7)
    g = csr_from_edges(src, dst, N)
    lgs = sample_layer_graphs(g, fanout=FANOUT, n_layers=L, seed=3)
    X = np.random.default_rng(1).standard_normal((N, D)).astype(np.float32)
    return lgs, X


def _params(model, heads=4):
    key = jax.random.PRNGKey(0)
    return {"gcn": lambda: init_gcn(key, DIMS),
            "sage": lambda: init_sage(key, DIMS),
            "gat": lambda: init_gat(key, DIMS, heads=heads)}[model]()


def test_ref_executor_matches_manual_gcn(world):
    """Guard the spec against drift: hand-rolled jnp math inline."""
    lgs, X = world
    params = _params("gcn")
    got = np.asarray(run_model(
        RefExecutor(), model_spec("gcn", params),
        [DenseIO.from_layer_graph(lg) for lg in lgs], X))
    H = jnp.asarray(X)
    for l, w in enumerate(params["w"]):
        lg = lgs[l]
        wts = jnp.asarray(mean_weights(lg.mask))
        H = jnp.dot(H, w, preferred_element_type=jnp.float32)
        vals = jnp.take(H, jnp.asarray(lg.nbr).reshape(-1), axis=0)
        vals = vals.reshape(lg.nbr.shape + (H.shape[-1],))
        H = (vals * (wts * lg.mask)[..., None]).sum(axis=1)
        if l < L - 1:
            H = jax.nn.relu(H)
    np.testing.assert_allclose(got, np.asarray(H), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("model", ["gcn", "sage", "gat"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_matches_ref(world, model, dtype):
    lgs, X = world
    params = _params(model)
    Xd = jnp.asarray(X, dtype)
    spec = model_spec(model, params)
    want, got = (np.asarray(run_model(ex, spec, ex.bind(lgs, spec), Xd),
                            np.float32)
                 for ex in (RefExecutor(), PallasExecutor()))
    np.testing.assert_allclose(got, want, atol=ATOL[dtype], rtol=3e-2)


@pytest.mark.parametrize("model", ["gcn", "sage", "gat"])
def test_spec_single_definition(model):
    """Every engine consumes the same spec object shape — one definition
    of the layer math per model."""
    spec = model_spec(model, _params(model))
    assert len(spec.layers) == L
    kinds = [op.kind for op in spec.layers[0].ops]
    assert kinds == {"gcn": ["gemm", "spmm"],
                     "sage": ["spmm", "gemm", "gemm", "add"],
                     "gat": ["gemm", "gemm", "gemm",
                             "attn_scores_softmax", "attend"]}[model]


@pytest.mark.parametrize("executor", ["ref", "pallas"])
def test_bind_then_run_model_is_the_session_epoch(executor):
    """``run_model(ex, spec, ex.bind(lgs, spec), X)`` is the one forward
    driver: outside a Session it gives ``Session.infer_all``'s bits."""
    from repro.api import DealConfig, Session
    cfg = DealConfig.from_dict({
        "graph": {"dataset": "rmat", "n_nodes": 200, "avg_degree": 8,
                  "fanout": 6, "seed": 4},
        "model": {"name": "gat", "n_layers": 2, "d_feature": 32,
                  "heads": 4},
        "executor": {"name": executor}})
    with Session.build(cfg) as s:
        want = s.infer_all().copy()
        ex = s.executor
        spec = model_spec("gat", s.params)
        got = np.asarray(run_model(ex, spec, ex.bind(s.layer_graphs, spec),
                                   s.X))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("executor", ["ref", "pallas"])
def test_fetch_on_one_device_is_np_asarray(executor):
    """One device holds H in whole rows already: ``Session.infer_all``'s
    fetch is ``np.asarray(H)`` and never counts a relayout."""
    from repro.api import DealConfig, Session
    cfg = DealConfig.from_dict({
        "graph": {"dataset": "rmat", "n_nodes": 200, "avg_degree": 8,
                  "fanout": 6, "seed": 4},
        "model": {"name": "gat", "n_layers": 2, "d_feature": 32,
                  "heads": 4},
        "executor": {"name": executor},
        "telemetry": {"enabled": True}})
    with Session.build(cfg) as s:
        got = s.infer_all()
        ex = s.executor
        spec = model_spec("gat", s.params)
        H = run_model(ex, spec, ex.bind(s.layer_graphs, spec), s.X)
        fetched = ex.fetch(H)
        m = s.telemetry.metrics.to_dict()
    np.testing.assert_array_equal(fetched, np.asarray(H))
    np.testing.assert_array_equal(got, fetched)
    assert "dist.fetch_relayouts" not in m


@pytest.mark.parametrize("model", ["gcn", "gat"])
def test_delta_refresh_pallas_bitwise(world, model):
    """Delta refresh through the pallas executor == full epoch through
    the pallas executor, bitwise (mirrors the ref-executor guarantee);
    GAT's attend takes the fused head-major ``gather_spmm`` route."""
    from repro.gnnserve import (DeltaReinference, MutationLog,
                                apply_edge_mutations, store_from_inference)
    src, dst = rmat_edges(128, 128 * 8, seed=5)
    g = csr_from_edges(src, dst, 128)
    lgs = sample_layer_graphs(g, fanout=4, n_layers=2, seed=2)
    rng = np.random.default_rng(0)
    X = rng.standard_normal((128, 32)).astype(np.float32)
    params = {"gcn": init_gcn, "gat": init_gat}[model](
        jax.random.PRNGKey(1), [32, 32, 32])
    ri = DeltaReinference([copy.deepcopy(l) for l in lgs], model, params,
                          executor="pallas")
    levels = ri.full_levels(X)
    store = store_from_inference(X, levels[1:], n_shards=4)
    log = MutationLog()
    log.add_edges(rng.integers(0, 128, 6), rng.integers(0, 128, 6))
    batch = log.drain()
    g2 = apply_edge_mutations(g, batch)
    ri.refresh(store, g2, batch.feat_ids, batch.feat_rows,
               batch.affected_dsts())
    oracle = DeltaReinference(ri.layer_graphs, model, params,
                              executor="pallas").full_levels(X)
    for lvl in range(1, 3):
        np.testing.assert_array_equal(store.lookup(np.arange(128), lvl),
                                      oracle[lvl])


def test_executor_factory():
    assert isinstance(get_executor("ref"), RefExecutor)
    assert isinstance(get_executor("pallas"), PallasExecutor)
    ex = RefExecutor()
    assert get_executor(ex) is ex
    with pytest.raises(ValueError):
        get_executor("dist")            # needs a mesh
    with pytest.raises(ValueError):
        get_executor("nope")
