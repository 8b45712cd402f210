"""Partitioner invariants: every sampled edge lands in exactly one group,
send/recv sets are consistent, comm volumes ordered as the paper claims."""
import numpy as np
import pytest

from repro.core.partition import build_plan, comm_volume


@pytest.mark.parametrize("P,M", [(2, 1), (4, 2), (8, 2)])
def test_edge_coverage(P, M, layer_graphs):
    plan = build_plan(layer_graphs, P, M)
    for li, lp in enumerate(plan.layers):
        lg = layer_graphs[li]
        n_local = lp.n_local
        covered = np.zeros(lg.nbr.shape, bool)
        for p in range(P):
            for k in range(P):
                m = lp.edge_mask[p, k]
                d = lp.edge_dst[p, k][m] + p * n_local
                s = lp.edge_slot[p, k][m]
                assert not covered[d, s].any(), "edge in two groups"
                covered[d, s] = True
        assert np.array_equal(covered, lg.mask)


def _full_tables(lg, P):
    """(slot_src, mask, target ids, real-row flags, global ids of [local
    tile; ring buffers]) per partition of ``build_plan``."""
    lp = build_plan([lg], P, 1).layers[0]
    n = lp.n_local
    for p in range(P):
        bufs = [lp.send_local[(p + k) % P, k] + (p + k) % P * n
                for k in range(1, P)]
        yield (lp.slot_src[p], lg.mask[p * n:(p + 1) * n],
               np.arange(p * n, (p + 1) * n), np.ones(n, bool),
               np.concatenate([np.arange(p * n, (p + 1) * n)] + bufs))


def _subset_tables(lg, P):
    """The same for a ``build_subset_plan`` over every third row."""
    from repro.core.partition import build_subset_plan
    sp = build_subset_plan(lg, np.arange(0, lg.n_nodes, 3), P)
    Rmax = sp.row_ids.shape[1]
    for p in range(P):
        bufs = [sp.src_ids[(p + k) % P][sp.send_local[(p + k) % P, k]]
                for k in range(1, P)]
        yield (sp.slot_src[p], sp.row_mask[p], sp.row_ids[p],
               np.isin(p * Rmax + np.arange(Rmax), sp.take),
               np.concatenate([sp.src_ids[p]] + bufs))


@pytest.mark.parametrize("tables", [_full_tables, _subset_tables],
                         ids=["build_plan", "build_subset_plan"])
@pytest.mark.parametrize("P", [2, 4])
def test_recv_buffer_resolves_to_right_rows(P, tables, layer_graphs):
    """Every masked-in slot of ``slot_src`` addresses, in [local tile;
    ring buffers 1..P-1] as ``send_local`` fills them, the row of global
    node ``nbr[row, slot]``; every other slot reads row 0 and is masked
    (as the graph masks it, and wholly on a pad row)."""
    for lg in layer_graphs:
        for slot_src, mask, ids, real, table in tables(lg, P):
            assert np.array_equal(mask[real], lg.mask[ids[real]])
            assert not mask[~real].any()
            assert np.array_equal(table[slot_src][mask], lg.nbr[ids][mask])
            assert not slot_src[~mask].any()


def test_unique_rows_fewer_than_edges(layer_graphs):
    """DEAL's win: requested unique rows <= duplicated per-edge rows."""
    plan = build_plan(layer_graphs, 4, 2)
    vols = comm_volume(plan, d_feature=64)
    for v in vols.values():
        assert v["unique_rows"] <= v["duplicated_edge_rows"]
        assert v["deal_feature_exchange_B"] <= v["graph_exchange_B"]


def test_bad_partition_rejected(layer_graphs):
    with pytest.raises(AssertionError):
        build_plan(layer_graphs, 7, 1)   # 256 % 7 != 0


def test_subset_plan_cache_hits_and_invalidation(layer_graphs):
    """Repeated recompute of the same hot frontier must reuse the cached
    plan (signature: sorted row ids + partition geometry); an in-place
    resample must invalidate it."""
    import copy

    from repro.core.partition import (SUBSET_PLAN_CACHE,
                                      build_subset_plan,
                                      build_subset_plan_cached,
                                      invalidate_subset_plans)
    lg = copy.deepcopy(layer_graphs[0])
    rows = np.arange(0, lg.n_nodes, 3, dtype=np.int64)
    before = dict(SUBSET_PLAN_CACHE)
    p1 = build_subset_plan_cached(lg, rows, 4)
    assert SUBSET_PLAN_CACHE["misses"] == before["misses"] + 1
    p2 = build_subset_plan_cached(lg, rows, 4)
    assert SUBSET_PLAN_CACHE["hits"] == before["hits"] + 1
    assert p2 is p1
    # cached plan is the real plan
    fresh = build_subset_plan(lg, rows, 4)
    np.testing.assert_array_equal(p1.row_ids, fresh.row_ids)
    np.testing.assert_array_equal(p1.slot_src, fresh.slot_src)
    np.testing.assert_array_equal(p1.send_local, fresh.send_local)

    # different frontier or geometry -> different cache slot
    assert build_subset_plan_cached(lg, rows[:-1], 4) is not p1
    assert build_subset_plan_cached(lg, rows, 2) is not p1
    assert build_subset_plan_cached(lg, rows, 4) is p1   # p1 still cached

    # in-place mutation (what resample_rows does) must invalidate
    invalidate_subset_plans(lg)
    assert build_subset_plan_cached(lg, rows, 4) is not p1


def test_resample_rows_invalidates_subset_plans(layer_graphs, small_graph):
    """The delta engine's resample path must not serve stale plans."""
    import copy

    from repro.core.partition import build_subset_plan_cached
    from repro.gnnserve import resample_rows
    lgs = [copy.deepcopy(lg) for lg in layer_graphs]
    rows = np.arange(0, lgs[0].n_nodes, 2, dtype=np.int64)
    p1 = build_subset_plan_cached(lgs[0], rows, 4)
    resample_rows(small_graph, lgs, rows[:5], seed=9)
    p2 = build_subset_plan_cached(lgs[0], rows, 4)
    assert p2 is not p1


def test_plan_bucket_keeps_three_significant_bits():
    from repro.core.partition import plan_bucket
    assert [plan_bucket(n) for n in (1, 8, 9, 16, 17, 18, 19)] == \
        [8, 8, 9, 16, 18, 18, 20]
    for n in range(1, 5000):
        b = plan_bucket(n)
        assert n <= b and (b <= 8 or b - n < b / 8)


def test_plan_shapes_do_not_depend_on_the_graph_seed():
    """Two seeds at one size sample graphs whose raw per-peer request
    and edge counts differ; the padded plan arrays have one shape, so
    one compiled program serves both."""
    from repro.core.graph import csr_from_edges, rmat_edges
    from repro.core.sampler import sample_layer_graphs

    def plan_and_counts(seed):
        src, dst = rmat_edges(4096, 4096 * 8, seed=seed)
        lgs = sample_layer_graphs(csr_from_edges(src, dst, 4096), fanout=8,
                                  n_layers=2, seed=seed)
        plan = build_plan(lgs, 2, 2)
        return plan, [(int(lp.send_count.max()), int(lp.edge_mask.sum(
            axis=-1).max())) for lp in plan.layers]

    (a, raw_a), (b, raw_b) = plan_and_counts(11), plan_and_counts(12)
    assert raw_a != raw_b
    for la, lb in zip(a.layers, b.layers):
        for name in ("send_local", "slot_src", "edge_dst", "edge_slot",
                     "edge_mask", "mirror_src"):
            assert getattr(la, name).shape == getattr(lb, name).shape
