"""Layer-wise engine vs ego-batched baseline: identical embeddings, and the
baseline provably does redundant work (the waste DEAL removes)."""
import jax
import numpy as np
import pytest

from repro.core.gnn_models import init_gat, init_gcn, init_sage, model_spec
from repro.core.layerwise import ego_batched_gcn_infer
from repro.core.ops import RefExecutor, run_model


def _epoch(model, lgs, X, params):
    ex, spec = RefExecutor(), model_spec(model, params)
    return run_model(ex, spec, ex.bind(lgs, spec), X)


@pytest.fixture(scope="module")
def feats(layer_graphs):
    rng = np.random.default_rng(1)
    N = layer_graphs[0].n_nodes
    return rng.standard_normal((N, 32), dtype=np.float32)


def test_ego_batched_matches_layerwise(layer_graphs, feats):
    params = init_gcn(jax.random.PRNGKey(0), [32, 32, 16])
    lgs = layer_graphs[:2]
    want = np.asarray(_epoch("gcn", lgs, feats, params))
    got, work = ego_batched_gcn_infer(lgs, feats, params, batch_size=64)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4, rtol=1e-4)


def test_ego_batched_redundancy(layer_graphs, feats):
    """Smaller batches -> strictly more GEMM rows than DEAL's k*N."""
    params = init_gcn(jax.random.PRNGKey(0), [32, 32, 16])
    lgs = layer_graphs[:2]
    N = lgs[0].n_nodes
    _, work_small = ego_batched_gcn_infer(lgs, feats, params, batch_size=16)
    _, work_big = ego_batched_gcn_infer(lgs, feats, params, batch_size=N)
    deal_work = 2 * N
    assert work_small > work_big >= deal_work


@pytest.mark.parametrize("model", ["gcn", "gat", "sage"])
def test_local_engines_finite(model, layer_graphs, feats):
    key = jax.random.PRNGKey(0)
    dims = [32, 32, 16]
    params = {"gcn": init_gcn(key, dims),
              "gat": init_gat(key, dims, heads=4),
              "sage": init_sage(key, dims)}[model]
    H = _epoch(model, layer_graphs[:2], feats, params)
    assert H.shape == (layer_graphs[0].n_nodes, 16)
    assert np.isfinite(np.asarray(H)).all()


def test_sharing_analytics(layer_graphs):
    from repro.core.sharing import sharing_table, sharing_vs_batch_size
    t = sharing_table(layer_graphs, batch_size=32)
    assert t["deal"] == 1.0
    assert 0.0 <= t["p3"] <= t["dgi_batched"] <= 1.0
    curve = sharing_vs_batch_size(layer_graphs,
                                  fractions=(0.05, 0.25, 1.0))
    vals = list(curve.values())
    assert vals == sorted(vals), "sharing must grow with batch size"
    assert vals[-1] > 0.99   # single batch == full sharing


def test_feature_prep_equivalence(tmp_path):
    from repro.core.feature_prep import (fused_load, redistribute_load,
                                         scan_all_load, write_feature_files)
    N, D, M = 256, 16, 4
    files, feats = write_feature_files(str(tmp_path), N, D, n_files=8)
    w = np.random.default_rng(0).standard_normal((D, 8)).astype(np.float32)
    x1, s1 = scan_all_load(files, M, N, D)
    x2, s2 = redistribute_load(files, M, N, D)
    np.testing.assert_array_equal(x1, feats)
    np.testing.assert_array_equal(x2, feats)
    h1, s3 = fused_load(files, M, N, D, w)
    np.testing.assert_allclose(h1, feats @ w, atol=1e-5)
    assert s1["file_rows"] == M * N        # scans everything M times
    assert s2["file_rows"] == N            # reads once
    assert s3["net_rows"] == 0             # no shuffle pass at all
