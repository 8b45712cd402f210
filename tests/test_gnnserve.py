"""gnnserve subsystem: store semantics, CSR overlay splice, delta
re-inference bitwise equivalence, and the continuous-batching engine."""
import copy

import jax
import numpy as np
import pytest

from repro.core.gnn_models import init_gat, init_gcn, init_sage, model_spec
from repro.core.graph import csr_from_edges, rmat_edges
from repro.core.ops import DistExecutor, RefExecutor, run_model
from repro.core.sampler import sample_layer_graphs
from repro.gnnserve import (DeltaReinference, EmbeddingServeEngine,
                            EmbeddingStore, MutationLog, Query,
                            apply_edge_mutations, store_from_inference)

N, D, L, FANOUT = 512, 32, 3, 8


@pytest.fixture(scope="module")
def world():
    src, dst = rmat_edges(N, N * 8, seed=7)
    g = csr_from_edges(src, dst, N)
    lgs = sample_layer_graphs(g, fanout=FANOUT, n_layers=L, seed=3)
    rng = np.random.default_rng(1)
    X = rng.standard_normal((N, D), dtype=np.float32)
    return g, src, dst, lgs, X


def _params(model, key=None):
    key = key or jax.random.PRNGKey(0)
    dims = [D] * L + [16]
    return {"gcn": lambda: init_gcn(key, dims),
            "sage": lambda: init_sage(key, dims),
            "gat": lambda: init_gat(key, [D] * (L + 1), heads=4)}[model]()


def _mutate(rng, src, dst, n_edge=8, n_feat=3):
    log = MutationLog()
    log.add_edges(rng.integers(0, N, n_edge), rng.integers(0, N, n_edge))
    pick = rng.choice(src.size, n_edge, replace=False)
    log.remove_edges(src[pick], dst[pick])
    if n_feat:
        fid = rng.choice(N, n_feat, replace=False)
        log.update_features(fid, rng.standard_normal((n_feat, D),
                                                     dtype=np.float32))
    return log


# ----------------------------------------------------------------------
# store
# ----------------------------------------------------------------------

def test_store_roundtrip_and_double_buffer(world):
    *_, X = world
    h1 = np.arange(N * 8, dtype=np.float32).reshape(N, 8)
    store = EmbeddingStore([X, h1], n_shards=4)
    ids = np.array([0, 17, 200, N - 1])
    np.testing.assert_array_equal(store.lookup(ids, 0), X[ids])
    np.testing.assert_array_equal(store.lookup(ids, -1), h1[ids])

    store.begin_update()
    store.write_rows(1, ids, np.full((ids.size, 8), -5.0, np.float32))
    # readers still see the committed front buffer
    np.testing.assert_array_equal(store.lookup(ids, 1), h1[ids])
    # the staged view reads through
    assert (store.lookup_staged(ids, 1) == -5.0).all()
    v0 = store.version
    store.commit()
    assert store.version == v0 + 1
    assert (store.lookup(ids, 1) == -5.0).all()
    # untouched rows of the dirtied shard survive the copy-on-write
    others = np.array([1, 18, 201])
    np.testing.assert_array_equal(store.lookup(others, 1), h1[others])

    store.begin_update()
    store.write_rows(1, ids, np.zeros((ids.size, 8), np.float32))
    store.abort()
    assert (store.lookup(ids, 1) == -5.0).all()


# ----------------------------------------------------------------------
# mutation overlay
# ----------------------------------------------------------------------

def test_apply_edge_mutations_matches_rebuild(world):
    g, src, dst, *_ = world
    rng = np.random.default_rng(5)
    log = _mutate(rng, src, dst, n_edge=32, n_feat=0)
    batch = log.drain()
    g2 = apply_edge_mutations(g, batch)

    # oracle: edit the edge list and rebuild the CSR from scratch
    edges = {(int(s), int(d)) for s, d in zip(src, dst)}
    kept = [(int(s), int(d)) for s, d in zip(src, dst)]
    for s, d in zip(batch.del_src, batch.del_dst):
        if (int(s), int(d)) in edges:
            kept.remove((int(s), int(d)))
    kept += list(zip(batch.add_src.tolist(), batch.add_dst.tolist()))
    g3 = csr_from_edges(np.array([e[0] for e in kept]),
                        np.array([e[1] for e in kept]), N)
    np.testing.assert_array_equal(g2.indptr, g3.indptr)
    for v in range(N):          # per-row multiset equality
        assert sorted(g2.neighbors(v).tolist()) == \
            sorted(g3.neighbors(v).tolist()), v


def test_add_then_remove_same_edge_nets_out(world):
    """Intra-batch op order is honored: add-then-remove of an edge not in
    the base graph must be a no-op, and remove-then-add must keep it."""
    g, *_ = world
    v = 0
    before = sorted(g.neighbors(v).tolist())
    absent = N - 1 if (N - 1) not in before else N - 2
    log = MutationLog()
    log.add_edge(absent, v)
    log.remove_edge(absent, v)
    g2 = apply_edge_mutations(g, log.drain())
    assert sorted(g2.neighbors(v).tolist()) == before

    log = MutationLog()
    log.remove_edge(absent, v)      # no-op: not present yet
    log.add_edge(absent, v)
    g3 = apply_edge_mutations(g, log.drain())
    assert sorted(g3.neighbors(v).tolist()) == sorted(before + [absent])


def test_remove_missing_edge_is_noop(world):
    g, *_ = world
    log = MutationLog()
    log.remove_edge(int(g.indices[0]) + 1, 0)   # likely absent pair
    before = g.neighbors(0).copy()
    g2 = apply_edge_mutations(g, log.drain())
    got = g2.neighbors(0)
    assert sorted(got.tolist()) == sorted(before.tolist()) or \
        len(got) == len(before) - 1


# ----------------------------------------------------------------------
# delta re-inference
# ----------------------------------------------------------------------

@pytest.mark.parametrize("model", ["gcn", "sage", "gat"])
def test_delta_refresh_bitwise_matches_full(world, model):
    g, src, dst, lgs, X = world
    params = _params(model)
    ri = DeltaReinference([copy.deepcopy(l) for l in lgs], model, params)
    levels = ri.full_levels(X)
    # sanity: full_levels agrees bitwise with the existing local engine
    ex, spec = RefExecutor(), model_spec(model, params)
    want = np.asarray(run_model(ex, spec, ex.bind(lgs, spec), X))
    np.testing.assert_array_equal(levels[-1], want)

    store = store_from_inference(X, levels[1:], n_shards=4)
    rng = np.random.default_rng(11)
    batch = _mutate(rng, src, dst).drain()
    g2 = apply_edge_mutations(g, batch)
    stats = ri.refresh(store, g2, batch.feat_ids, batch.feat_rows,
                       batch.affected_dsts())
    assert stats["version"] == 1
    assert 0 < stats["frontier_sizes"][-1] <= N

    # oracle: from-scratch recompute over the SAME mutated layer graphs
    X2 = X.copy()
    X2[batch.feat_ids] = batch.feat_rows
    oracle = DeltaReinference(ri.layer_graphs, model, params).full_levels(X2)
    all_ids = np.arange(N)
    for lvl in range(1, ri.n_layers + 1):
        got = store.lookup(all_ids, lvl)
        np.testing.assert_array_equal(got, oracle[lvl])  # bitwise, ALL rows


def test_refresh_batching_is_invariant(world):
    """Folding one mutation stream in one batch or two lands on
    bitwise-identical store bytes (content-addressed resample seeding) —
    the property the QoS engine's per-tenant freshness views rely on."""
    g, src, dst, lgs, X = world
    params = _params("gcn")
    rng = np.random.default_rng(41)
    logs = [_mutate(np.random.default_rng(s), src, dst) for s in (1, 2)]
    batches = [lg_.drain() for lg_ in logs]

    def fold(batch_seq):
        ri = DeltaReinference([copy.deepcopy(l) for l in lgs], "gcn",
                              params)
        store = store_from_inference(X, ri.full_levels(X)[1:], n_shards=4)
        gm = g
        for b in batch_seq:
            gm = apply_edge_mutations(gm, b)
            ri.refresh(store, gm, b.feat_ids, b.feat_rows,
                       b.affected_dsts())
        return store

    # one big batch: replay both logs into a single drain
    big = MutationLog()
    for b in batches:
        big.requeue(b)
    split, whole = fold(batches), fold([big.drain()])
    all_ids = np.arange(N)
    for lvl in range(L + 1):
        np.testing.assert_array_equal(split.lookup(all_ids, lvl),
                                      whole.lookup(all_ids, lvl))


def test_reverse_index_splice_equals_rebuild(world):
    """`splice_reverse_index` over the resampled rows' old/new entries
    must equal a from-scratch `build_reverse_index`, indptr and rows
    bitwise, across chained mutations."""
    from repro.gnnserve import (build_reverse_index, resample_rows,
                                splice_reverse_index)
    g, src, dst, lgs, X = world
    lgs2 = [copy.deepcopy(l) for l in lgs]
    rev = [build_reverse_index(lg) for lg in lgs2]
    rng = np.random.default_rng(3)
    gm = g
    for _ in range(3):
        batch = _mutate(rng, src, dst, n_edge=12, n_feat=0).drain()
        gm = apply_edge_mutations(gm, batch)
        rows = batch.affected_dsts()
        old = [(lg.nbr[rows].copy(), lg.mask[rows].copy()) for lg in lgs2]
        resample_rows(gm, lgs2, rows, seed=0)
        for l, lg in enumerate(lgs2):
            rev[l] = splice_reverse_index(rev[l], rows, old[l][0],
                                          old[l][1], lg.nbr[rows],
                                          lg.mask[rows])
            fresh = build_reverse_index(lg)
            np.testing.assert_array_equal(rev[l].indptr, fresh.indptr)
            np.testing.assert_array_equal(rev[l].rows, fresh.rows)


def test_refresh_maintains_reverse_index_incrementally(world):
    """After the first refresh builds the reverse indexes, later mutated
    refreshes SPLICE them (O(changed)) instead of rebuilding (O(N*F))."""
    g, src, dst, lgs, X = world
    params = _params("gcn")
    ri = DeltaReinference([copy.deepcopy(l) for l in lgs], "gcn", params)
    store = store_from_inference(X, ri.full_levels(X)[1:], n_shards=4)
    rng = np.random.default_rng(13)
    gm = g
    for it in range(3):
        batch = _mutate(rng, src, dst).drain()
        gm = apply_edge_mutations(gm, batch)
        ri.refresh(store, gm, batch.feat_ids, batch.feat_rows,
                   batch.affected_dsts())
    # first refresh lazily rebuilt each layer's index; the next two
    # spliced it in place of the old full-rebuild-every-refresh path
    assert ri.rev_rebuilds == ri.n_layers
    assert ri.rev_splices == 2 * ri.n_layers
    from repro.gnnserve import build_reverse_index
    for l, lg in enumerate(ri.layer_graphs):
        fresh = build_reverse_index(lg)
        np.testing.assert_array_equal(ri._rev[l].indptr, fresh.indptr)
        np.testing.assert_array_equal(ri._rev[l].rows, fresh.rows)


def test_frontier_is_complete(world):
    """Every row the mutation actually changed is inside the frontier —
    rows outside it were provably safe to skip."""
    g, src, dst, lgs, X = world
    params = _params("gcn")
    ri = DeltaReinference([copy.deepcopy(l) for l in lgs], "gcn", params)
    before = ri.full_levels(X)
    store = store_from_inference(X, before[1:], n_shards=4)
    rng = np.random.default_rng(23)
    batch = _mutate(rng, src, dst).drain()
    g2 = apply_edge_mutations(g, batch)
    stats = ri.refresh(store, g2, batch.feat_ids, batch.feat_rows,
                       batch.affected_dsts())
    after = DeltaReinference(ri.layer_graphs, "gcn", params).full_levels(
        store.lookup(np.arange(N), 0))
    final_frontier = stats["frontier_sizes"][-1]
    changed = np.nonzero((before[-1] != after[-1]).any(axis=1))[0]
    assert changed.size <= final_frontier
    # and delta never recomputed everything for this tiny batch
    assert final_frontier < N


# ----------------------------------------------------------------------
# serve engine
# ----------------------------------------------------------------------

def _engine(world, staleness_bound=4):
    g, src, dst, lgs, X = world
    params = _params("gcn")
    ri = DeltaReinference([copy.deepcopy(l) for l in lgs], "gcn", params)
    levels = ri.full_levels(X)
    store = store_from_inference(X, levels[1:], n_shards=4)
    eng = EmbeddingServeEngine(store, ri, g, batch_slots=3,
                               rows_per_step=32,
                               staleness_bound=staleness_bound)
    return eng, levels


def test_engine_serves_correct_rows(world):
    eng, levels = _engine(world)
    rng = np.random.default_rng(3)
    qs = [Query(uid=i, node_ids=rng.choice(N, 100, replace=False))
          for i in range(7)]
    for q in qs:
        eng.submit(q)
    eng.run()
    assert all(q.done for q in qs)
    for q in qs:
        np.testing.assert_array_equal(q.out, levels[-1][q.node_ids])
    s = eng.stats()
    assert s["n_served"] == 7 and s["n_refreshes"] == 0
    # continuous batching: way fewer gather steps than per-query serial
    assert s["n_gather_steps"] < 7 * (100 // 10)


def test_engine_staleness_triggers_refresh(world):
    g, src, dst, lgs, X = world
    eng, levels = _engine(world, staleness_bound=4)
    rng = np.random.default_rng(9)
    # 2 pending mutations: below the bound, serving stays stale
    eng.mutate().add_edges(rng.integers(0, N, 2), rng.integers(0, N, 2))
    q1 = Query(uid=0, node_ids=np.arange(50))
    eng.submit(q1)
    eng.run()
    assert eng.n_refreshes == 0 and q1.served_version == 0
    # crossing the bound forces a refresh before the next gather
    eng.mutate().add_edges(rng.integers(0, N, 5), rng.integers(0, N, 5))
    q2 = Query(uid=1, node_ids=np.arange(50))
    eng.submit(q2)
    eng.run()
    assert eng.n_refreshes == 1 and eng.store.version == 1
    assert q2.served_version == 1 and eng.staleness == 0
    # served rows match a from-scratch epoch over the refreshed state
    oracle = DeltaReinference(eng.reinfer.layer_graphs, "gcn",
                              eng.reinfer.params).full_levels(
        eng.store.lookup(np.arange(N), 0))
    np.testing.assert_array_equal(q2.out, oracle[-1][q2.node_ids])


def test_failed_refresh_preserves_log_and_rolls_back(world):
    """A bad batch must neither discard the good mutations drained with
    it nor leave layer graphs and store out of sync."""
    g, src, dst, lgs, X = world
    eng, _ = _engine(world, staleness_bound=1)
    eng.mutate().add_edge(N + 5, 0)                 # invalid source id
    eng.mutate().update_features(
        np.array([1, 2]), np.random.default_rng(2).standard_normal(
            (2, D), dtype=np.float32))
    before = eng.staleness
    with pytest.raises(AssertionError):
        eng.refresh()
    assert eng.staleness == before                  # nothing lost
    assert eng.store.version == 0                   # nothing committed

    # a failure INSIDE the store transaction rolls the resample back too:
    # a later clean refresh must leave store == from-scratch epoch
    ri, store = eng.reinfer, eng.store
    log = MutationLog()
    log.add_edges(np.array([5, 6]), np.array([7, 8]))
    batch = log.drain()
    g2 = apply_edge_mutations(g, batch)
    with pytest.raises(ValueError):
        ri.refresh(store, g2, np.array([0]),
                   np.zeros((1, 99), np.float32),   # wrong feature width
                   batch.affected_dsts())
    ri.refresh(store, g2, batch.feat_ids, batch.feat_rows,
               batch.affected_dsts())
    oracle = DeltaReinference(ri.layer_graphs, "gcn",
                              ri.params).full_levels(
        store.lookup(np.arange(N), 0))
    for lvl in range(1, ri.n_layers + 1):
        np.testing.assert_array_equal(store.lookup(np.arange(N), lvl),
                                      oracle[lvl])


def test_mid_query_refresh_serves_one_epoch(world):
    """A refresh landing while a query is mid-gather must not tear the
    response across epochs: every row comes from the pinned snapshot."""
    g, src, dst, lgs, X = world
    eng, levels = _engine(world, staleness_bound=2)
    eng.rows_per_step = 16
    q = Query(uid=0, node_ids=np.arange(64))
    eng.submit(q)
    eng.step()                                      # rows 0..15 at v0
    rng = np.random.default_rng(3)
    eng.mutate().add_edges(rng.integers(0, N, 4), rng.integers(0, N, 4))
    eng.run()                                       # refresh fires mid-query
    assert eng.store.version == 1
    assert q.served_version == 0                    # pinned at first gather
    np.testing.assert_array_equal(q.out, levels[-1][q.node_ids])


def test_engine_fresh_query_and_node_adds(world):
    eng, _ = _engine(world, staleness_bound=10_000)
    rng = np.random.default_rng(13)
    eng.mutate().add_edges(rng.integers(0, N, 3), rng.integers(0, N, 3))
    q = Query(uid=0, node_ids=np.arange(10), fresh=True)
    eng.submit(q)
    eng.run()
    assert q.done and q.served_version == 1 and eng.n_refreshes == 1

    eng.mutate().add_nodes(2)
    eng.submit(Query(uid=1, node_ids=np.arange(4), fresh=True))
    with pytest.raises(NotImplementedError):
        eng.run()


# ----------------------------------------------------------------------
# frontier-size cutover (dist -> local routing for tiny frontiers)
# ----------------------------------------------------------------------

class _FakeDist(DistExecutor):
    """A DistExecutor by type only: any mesh work explodes.  Lets the
    cutover tests prove which route a layer actually took without
    spinning up a mesh subprocess."""

    def __init__(self):          # no mesh, no plan
        pass

    def run_rows(self, *a, **k):
        raise AssertionError("dist path taken")


def test_cutover_routes_tiny_frontiers_local(world):
    """With the threshold above every universe size, all layers run on
    the lazily-built local executor — bitwise-equal to a ref-executor
    refresh — and the counters record the routing decision."""
    g, src, dst, lgs, X = world
    params = _params("gcn")
    twins = {}
    for name, ex, cut in (("cut", _FakeDist(), 10 ** 9), ("ref", "ref", 0)):
        ri = DeltaReinference([copy.deepcopy(l) for l in lgs], "gcn",
                              params, executor=ex, local_cutover=cut)
        store = store_from_inference(
            X, DeltaReinference(lgs, "gcn", params).full_levels(X)[1:],
            n_shards=4)
        g2 = g
        rng = np.random.default_rng(11)
        for _ in range(2):
            batch = _mutate(rng, src, dst).drain()
            g2 = apply_edge_mutations(g2, batch)
            stats = ri.refresh(store, g2, batch.feat_ids, batch.feat_rows,
                               batch.affected_dsts())
        twins[name] = (store, stats)
    store_c, stats_c = twins["cut"]
    store_r, _ = twins["ref"]
    assert stats_c["n_local_cutovers"] > 0
    assert stats_c["n_dist_layers"] == 0
    assert stats_c["local_cutover"] == 10 ** 9
    ids = np.arange(N)
    for lvl in range(L + 1):
        np.testing.assert_array_equal(store_c.lookup(ids, lvl),
                                      store_r.lookup(ids, lvl))


def test_cutover_disabled_takes_dist_path(world):
    """local_cutover=0 (the default) must leave routing untouched —
    run_rows is reached, preserving dist-vs-dist bitwise equivalence."""
    g, src, dst, lgs, X = world
    params = _params("gcn")
    ri = DeltaReinference([copy.deepcopy(l) for l in lgs], "gcn", params,
                          executor=_FakeDist())
    store = store_from_inference(
        X, DeltaReinference(lgs, "gcn", params).full_levels(X)[1:],
        n_shards=4)
    rng = np.random.default_rng(11)
    batch = _mutate(rng, src, dst).drain()
    g2 = apply_edge_mutations(g, batch)
    with pytest.raises(AssertionError, match="dist path taken"):
        ri.refresh(store, g2, batch.feat_ids, batch.feat_rows,
                   batch.affected_dsts())
