"""Multi-device distributed correctness checks — run IN A SUBPROCESS so the
main pytest process keeps a single device (see conftest note).

Exit code 0 == all checks passed.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "src"))

import _heartbeat as hb  # noqa: E402

hb.init(sys.argv)

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import primitives as prim  # noqa: E402
from repro.core.gnn_models import (edge_softmax, init_gat,  # noqa: E402
                                   init_gcn, init_sage, mean_weights,
                                   model_spec)
from repro.core.graph import csr_from_edges, rmat_edges  # noqa: E402
from repro.core.ops import DistExecutor, RefExecutor, run_model  # noqa: E402
from repro.core.partition import build_plan  # noqa: E402
from repro.core.sampler import sample_layer_graphs  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402


def epoch(ex, model, params, lgs, X):
    """One all-node forward pass of ``model`` through ``ex``."""
    spec = model_spec(model, params)
    return run_model(ex, spec, ex.bind(lgs, spec), X)


def check(name, got, want, atol=2e-5):
    err = np.abs(np.asarray(got) - np.asarray(want)).max()
    ok = err <= atol
    print(f"{'OK ' if ok else 'FAIL'} {name}: max_err={err:.2e}")
    hb.beat(name)
    if not ok:
        sys.exit(1)


def main():
    P_, M_ = 4, 2
    mesh = make_host_mesh(P_, M_)
    N, D = 256, 64
    src, dst = rmat_edges(N, N * 8, seed=1)
    g = csr_from_edges(src, dst, N)
    lgs = sample_layer_graphs(g, fanout=8, n_layers=2, seed=0)
    rng = np.random.default_rng(0)
    X = rng.standard_normal((N, D), dtype=np.float32)
    W = rng.standard_normal((D, D), dtype=np.float32) * 0.1

    hd = NamedSharding(mesh, P("data", "model"))
    Xs = jax.device_put(jnp.asarray(X), hd)

    for variant in ("deal", "deal_ring", "cagnet"):
        gm = prim.make_gemm(mesh, variant)
        check(f"gemm/{variant}", gm(Xs, jnp.asarray(W)), X @ W, 5e-5)

    plan = build_plan(lgs, P_, M_)
    lp = plan.layers[0]
    dev = prim.plan_device_arrays(lp)
    w = mean_weights(lgs[0].mask)
    ws = jax.device_put(jnp.asarray(w), NamedSharding(mesh, P("data", None)))
    want = prim.ref_spmm(jnp.asarray(X), jnp.asarray(w),
                         jnp.asarray(lgs[0].nbr), jnp.asarray(lgs[0].mask))
    mask_f = jax.device_put(jnp.asarray(lgs[0].mask, jnp.float32),
                            NamedSharding(mesh, P("data", None)))
    deal_args = (mask_f, dev["send_local"], dev["slot_src"])
    for variant in ("deal", "graph_exchange", "allgather"):
        sp = prim.make_spmm(mesh, lp, variant)
        if variant == "allgather":
            nbr = jnp.asarray(lgs[0].nbr.reshape(P_, N // P_, -1))
            msk = jnp.asarray(lgs[0].mask.reshape(P_, N // P_, -1))
            got = sp(Xs, ws, nbr, msk)
        elif variant == "graph_exchange":
            got = sp(Xs, ws, dev["mirror_src"], dev["edge_dst"],
                     dev["edge_slot"], dev["edge_mask"])
        else:
            got = sp(Xs, ws, *deal_args)
        check(f"spmm/{variant}", got, want)

    q = rng.standard_normal((N, D), dtype=np.float32)
    qs = jax.device_put(jnp.asarray(q), hd)
    want_e = prim.ref_sddmm(jnp.asarray(q), jnp.asarray(X),
                            jnp.asarray(lgs[0].nbr),
                            jnp.asarray(lgs[0].mask))
    for variant in ("deal", "dup"):
        sd = prim.make_sddmm(mesh, lp, variant)
        check(f"sddmm/{variant}", sd(qs, Xs, *deal_args), want_e, 2e-4)

    dex, ref = DistExecutor(mesh), RefExecutor()
    for model, params in (
            ("gcn", init_gcn(jax.random.PRNGKey(0), [D, 64, 32])),
            ("gat", init_gat(jax.random.PRNGKey(1), [D, 64, 32], heads=1)),
            ("sage", init_sage(jax.random.PRNGKey(2), [D, 64, 32]))):
        check(f"engine/{model}", epoch(dex, model, params, lgs, X),
              epoch(ref, model, params, lgs, X), 5e-5)

    check_dist_delta(mesh, g, lgs, X)
    check_evict_equivalence(mesh, g, lgs, X)
    check_chunked_refresh(mesh, g, lgs, X)
    check_tail_onboarding(mesh, g, lgs, X)

    print("ALL DISTRIBUTED CHECKS PASSED")


def check_dist_delta(mesh, g, lgs, X):
    """Row-subset (frontier) execution on the mesh: DistExecutor-backed
    delta refresh must be BITWISE-equal to a full epoch through the same
    executor, for every model — the distributed-delta-refresh guarantee.
    """
    import copy

    from repro.gnnserve import (DeltaReinference, MutationLog,
                                apply_edge_mutations, store_from_inference)

    N, D = X.shape
    L = len(lgs)
    rng = np.random.default_rng(3)
    dex = DistExecutor(mesh)
    for model in ("gcn", "sage", "gat"):
        key = jax.random.PRNGKey(4)
        dims = [D] * L + [32]
        params = {"gcn": lambda: init_gcn(key, dims),
                  "sage": lambda: init_sage(key, dims),
                  "gat": lambda: init_gat(key, dims, heads=1)}[model]()
        ri = DeltaReinference([copy.deepcopy(l) for l in lgs], model,
                              params, executor=dex)
        levels = ri.full_levels(X)
        ref = DeltaReinference([copy.deepcopy(l) for l in lgs], model,
                               params).full_levels(X)
        check(f"delta_dist/{model}/full_levels_vs_ref",
              levels[-1], ref[-1], 5e-5)

        store = store_from_inference(X, levels[1:], n_shards=4)
        log = MutationLog()
        log.add_edges(rng.integers(0, N, 8), rng.integers(0, N, 8))
        fid = rng.choice(N, 3, replace=False)
        log.update_features(fid, rng.standard_normal(
            (3, D)).astype(np.float32))
        batch = log.drain()
        g2 = apply_edge_mutations(g, batch)
        stats = ri.refresh(store, g2, batch.feat_ids, batch.feat_rows,
                           batch.affected_dsts())
        assert 0 < stats["frontier_sizes"][-1] < N, stats
        X2 = X.copy()
        X2[batch.feat_ids] = batch.feat_rows
        oracle = DeltaReinference(ri.layer_graphs, model, params,
                                 executor=dex).full_levels(X2)
        for lvl in range(1, L + 1):
            got = store.lookup(np.arange(N), lvl)
            exact = bool((got == oracle[lvl]).all())
            print(f"{'OK ' if exact else 'FAIL'} delta_dist/{model}/"
                  f"level{lvl}: bitwise={exact} "
                  f"frontier={stats['frontier_sizes']}")
            if not exact:
                sys.exit(1)


def check_evict_equivalence(mesh, g, lgs, X):
    """Memory-budgeted store on the DIST executor: with residency capped
    at 50% then tightened to 25%, lookups and a mutated refresh
    (mid-refresh staged misses included) must serve rows bitwise-equal
    to an unbudgeted store — recompute-on-miss routes through
    ``DistExecutor.run_rows``.  Reads are SAMPLED (not full scans): each
    distinct recompute frontier compiles fresh collective geometries on
    the mesh, so full scans at every level would dominate the suite's
    wall clock without adding coverage.
    """
    import copy

    from repro.gnnserve import (DeltaReinference, MutationLog,
                                apply_edge_mutations, attach_recompute,
                                store_from_inference)

    N, D = X.shape
    L = len(lgs)
    dex = DistExecutor(mesh)
    for model in ("gcn", "sage", "gat"):
        rng = np.random.default_rng(11)
        key = jax.random.PRNGKey(4)
        dims = [D] * L + [32]
        params = {"gcn": lambda: init_gcn(key, dims),
                  "sage": lambda: init_sage(key, dims),
                  "gat": lambda: init_gat(key, dims, heads=1)}[model]()

        ri_o = DeltaReinference([copy.deepcopy(l) for l in lgs], model,
                                params, executor=dex)
        oracle = store_from_inference(X, ri_o.full_levels(X)[1:],
                                      n_shards=4)
        ri_b = DeltaReinference([copy.deepcopy(l) for l in lgs], model,
                                params, executor=dex)
        store = attach_recompute(
            store_from_inference(X, ri_b.full_levels(X)[1:], n_shards=4,
                                 budget_rows=N // 2), ri_b)

        def sampled_equal(tag):
            ids = np.sort(rng.choice(N, 96, replace=False))
            exact = all(bool((store.lookup(ids, lvl) ==
                              oracle.lookup(ids, lvl)).all())
                        for lvl in range(1, L + 1))
            st = store.stats()
            ok = exact and st["n_evictions"] > 0 and st["misses"] > 0
            print(f"{'OK ' if ok else 'FAIL'} evict_dist/{model}/{tag}: "
                  f"bitwise={exact} evictions={st['n_evictions']} "
                  f"misses={st['misses']} "
                  f"recomputed={st['rows_recomputed']}")
            if not ok:
                sys.exit(1)

        sampled_equal("budget0.5")
        log = MutationLog()
        log.add_edges(rng.integers(0, N, 8), rng.integers(0, N, 8))
        fid = rng.choice(N, 3, replace=False)
        log.update_features(fid, rng.standard_normal(
            (3, D)).astype(np.float32))
        batch = log.drain()
        g2 = apply_edge_mutations(g, batch)
        # lockstep refresh: both stores move version 0 -> 1, so the
        # deterministic resample draws the same rows; the budgeted one
        # recomputes its staged-overlay misses through run_rows
        ri_o.refresh(oracle, g2, batch.feat_ids, batch.feat_rows,
                     batch.affected_dsts())
        ri_b.refresh(store, g2, batch.feat_ids, batch.feat_rows,
                     batch.affected_dsts())
        sampled_equal("budget0.5+refresh")
        store.budget_rows = N // 4          # tighten: 50% -> 25%
        store._enforce_budget()
        sampled_equal("budget0.25")


def check_chunked_refresh(mesh, g, lgs, X):
    """Preemptible chunked refresh on the MESH: a ``begin_refresh`` job
    drained 13 rows at a time commits the exact bytes of the one-shot
    dist refresh — chunk boundaries never change which reduction
    produced a row's bits."""
    import copy

    from repro.gnnserve import (DeltaReinference, MutationLog,
                                apply_edge_mutations, store_from_inference)

    N, D = X.shape
    L = len(lgs)
    rng = np.random.default_rng(17)
    dex = DistExecutor(mesh)
    params = init_gcn(jax.random.PRNGKey(4), [D] * L + [32])
    log = MutationLog()
    log.add_edges(rng.integers(0, N, 12), rng.integers(0, N, 12))
    fid = rng.choice(N, 6, replace=False)
    log.update_features(fid, rng.standard_normal((6, D)).astype(np.float32))
    batch = log.drain()
    g2 = apply_edge_mutations(g, batch)

    stores = {}
    for chunk in (0, 13):
        ri = DeltaReinference([copy.deepcopy(l) for l in lgs], "gcn",
                              params, executor=dex)
        store = store_from_inference(X, ri.full_levels(X)[1:], n_shards=4)
        job = ri.begin_refresh(store, g2, batch.feat_ids, batch.feat_rows,
                               batch.affected_dsts(), chunk_rows=chunk)
        while not job.done:
            job.step()
        stats = job.finish()
        stores[chunk] = store
        if chunk:
            assert stats["n_chunks"] > L, stats
    for lvl in range(1, L + 1):
        exact = bool((stores[13].lookup(np.arange(N), lvl) ==
                      stores[0].lookup(np.arange(N), lvl)).all())
        print(f"{'OK ' if exact else 'FAIL'} chunked_dist/gcn/level{lvl}: "
              f"bitwise={exact}")
        hb.beat(f"chunked_dist/level{lvl}")
        if not exact:
            sys.exit(1)


def check_tail_onboarding(mesh, g, lgs, X):
    """onboarding="tail" THROUGH the dist executor: tail-partition rows
    (and rows sampling them) route through the local path while main
    rows keep the frozen mesh geometry — and the refreshed store is
    bitwise-equal to a full epoch through the same routed executor
    (``full_epoch`` is the oracle AND the fold)."""
    import copy

    from repro.gnnserve import (DeltaReinference, EmbeddingServeEngine,
                                store_from_inference)

    N, D = X.shape
    L = len(lgs)
    rng = np.random.default_rng(23)
    dex = DistExecutor(mesh)
    params = init_gcn(jax.random.PRNGKey(5), [D] * L + [32])
    ri = DeltaReinference([copy.deepcopy(l) for l in lgs], "gcn", params,
                          executor=dex)
    store = store_from_inference(X, ri.full_levels(X)[1:], n_shards=4,
                                 onboarding="tail")
    eng = EmbeddingServeEngine(store, ri, g, staleness_bound=4)
    k = 3
    eng.mutate().add_nodes(k, rng.standard_normal((k, D)).astype(np.float32))
    new = np.arange(N, N + k)
    eng.mutate().add_edges(rng.integers(0, N, 2 * k), np.repeat(new, 2))
    eng.mutate().add_edges(new, rng.integers(0, N, k))
    stats = eng.refresh()
    assert stats["n_onboarded"] == k, stats
    assert ri.n_tail_routed > 0, "no rows took the tail-local route"
    assert ri.n_dist_layers > 0, "main rows left the mesh"
    # oracle: a full routed epoch over the CURRENT (grown) layer graphs
    # — same frozen n_main, so per-row reductions match the refresh
    X2 = eng.store.lookup(np.arange(N + k, dtype=np.int64), 0)
    oracle = ri.full_levels(X2)
    for lvl in range(1, L + 1):
        exact = bool((eng.store.lookup(np.arange(N + k), lvl) ==
                      oracle[lvl]).all())
        print(f"{'OK ' if exact else 'FAIL'} tail_dist/refresh/level{lvl}:"
              f" bitwise={exact} tail_routed={ri.n_tail_routed}")
        hb.beat(f"tail_dist/level{lvl}")
        if not exact:
            sys.exit(1)
    fold = eng.full_epoch()
    ok = (eng.store.n_tail_shards == 0
          and fold["version"] == eng.store.version
          and bool((eng.store.lookup(np.arange(N + k), -1) ==
                    oracle[-1]).all()))
    print(f"{'OK ' if ok else 'FAIL'} tail_dist/fold: "
          f"n_shards={eng.store.n_shards} bitwise={ok}")
    hb.beat("tail_dist/fold")
    if not ok:
        sys.exit(1)


# ----------------------------------------------------------------------
# multi-head GAT on the mesh: one case per check, each reported on its
# own line ("CASE <name> OK|FAIL <detail>") and none stopping the rest
# ----------------------------------------------------------------------

def _gat_cfg(heads, p, m, executor="dist", telemetry=False):
    from repro.api import DealConfig
    return DealConfig.from_dict({
        "graph": {"dataset": "rmat", "n_nodes": 256, "avg_degree": 8,
                  "fanout": 8, "seed": 5},
        "model": {"name": "gat", "n_layers": 2, "d_feature": 64,
                  "heads": heads},
        "partition": {"p": p, "m": m},
        "executor": {"name": executor, "fallback_to_ref": False},
        "telemetry": {"enabled": telemetry}})


def _infer(cfg):
    from repro.api import Session
    with Session.build(cfg) as s:
        return np.array(s.infer_all())


def case_session_vs_ref(heads, p, m):
    """DealConfig -> Session.infer_all on the mesh against RefExecutor,
    in a Session and by ``run_model`` alone, on the same seeded world.
    Tolerance 5e-5 (max|H| ~2.5): f32 sums in another order, the
    per-head dot over its lanes (split over shards and psummed where a
    head spans them) and the attend's ring accumulation over the
    fanout, against the oracle's einsums; a wrong head split errs by
    O(1)."""
    from repro.api import Session
    got = _infer(_gat_cfg(heads, p, m))
    ref = _infer(_gat_cfg(heads, p, m, executor="ref"))
    with Session.build(_gat_cfg(heads, p, m, executor="ref")) as s:
        local = np.asarray(epoch(RefExecutor(), "gat", s.params,
                                 s.layer_graphs, s.X))
    err = max(np.abs(got - ref).max(), np.abs(got - local).max())
    return err <= 5e-5, f"max_err={err:.2e} max|H|={np.abs(ref).max():.2f}"


def case_heads1_bitwise_parent():
    """heads=1 keeps the pre-per-head distributed GAT bit for bit: the
    same Session world through a test-local copy of the earlier
    full-width score (one dot over all D lanes, psum over ``model``,
    / sqrt(D)), then the masked softmax and the plain ring attend."""
    from repro.api import Session
    from repro.core.gnn_models import masked_softmax

    class FullWidthScores(DistExecutor):
        def attn_scores_softmax(self, q, k, io, heads):
            assert self.M % heads == 0
            scores = self._sddmm(q, k, *io.args) / np.sqrt(q.shape[1])
            return masked_softmax(scores, io.mask_f > 0)

        def attend(self, alpha, v, io, heads):
            return self._spmm(v, alpha, *io.args)

    with Session.build(_gat_cfg(1, 2, 2)) as s:
        got = np.array(s.infer_all())
        s.executor = FullWidthScores(s.executor.mesh)
        s._H = None
        want = np.array(s.infer_all())
    same = bool((got == want).all())
    return same, f"bitwise={same}"


def _edge_lists(lg, lp):
    """The edge-list form of one layer's plan, as the ring consumers read
    it before the slot table: per (device p, ring step k) the masked-in
    edges' target row, slot, row in [H_local | recv buffer k] (the
    plan's ``send_local`` order) and mask, padded to one length."""
    P_, n = lp.P, lp.n_local
    groups = {}
    for p in range(P_):
        nbr, mask = lg.nbr[p * n:(p + 1) * n], lg.mask[p * n:(p + 1) * n]
        for k in range(P_):
            q = (p + k) % P_
            d, s = np.nonzero(mask & (nbr // n == q))
            ids = nbr[d, s] - q * n
            if k:
                ids = np.searchsorted(
                    lp.send_local[q, k, :lp.send_count[q, k]], ids)
            groups[p, k] = (d, s, ids)
    E = max(g[0].size for g in groups.values())
    out = [np.zeros((P_, P_, E), np.int32) for _ in range(3)] + \
        [np.zeros((P_, P_, E), bool)]
    for (p, k), g in groups.items():
        for a, v in zip(out, g + (True,)):
            a[p, k, :g[0].size] = v
    return [jnp.asarray(a) for a in out]


def _scatter_programs(mesh, P_, heads, fanout):
    """Test-local copies of the edge-list consumers the slot table
    replaced: each group's rows scatter-added into their target rows
    (per-edge and head-major SPMM) and slots (per-head scores, scaled
    as ``dist_gat_attention`` scales them)."""
    M = mesh.shape["model"]
    c = heads // M
    plan = P("data", None, None)

    def spmm(H, w, send_local, dst, slot, pos, emask):
        send_local, dst, slot, pos, emask = (
            a[0] for a in (send_local, dst, slot, pos, emask))
        out = jnp.zeros((w.shape[0], H.shape[1]), jnp.float32)
        bufs = [H] + prim._ring_bufs(H, send_local, P_)
        for k, buf in enumerate(bufs):
            vals = jnp.take(buf, pos[k], axis=0).astype(jnp.float32)
            if w.ndim == 2:
                we = (w[dst[k], slot[k]] * emask[k]).astype(jnp.float32)
                vals = vals * we[:, None]
            else:
                we = (w[dst[k], slot[k]]
                      * emask[k][:, None]).astype(jnp.float32)
                vals = (vals.reshape(vals.shape[0], we.shape[1], -1)
                        * we[:, :, None]).reshape(vals.shape)
            out = out.at[dst[k]].add(vals)
        return out.astype(H.dtype)

    def scores(q, kf, send_local, dst, slot, pos, emask):
        send_local, dst, slot, pos, emask = (
            a[0] for a in (send_local, dst, slot, pos, emask))
        n_loc, d_loc = q.shape
        attn = jnp.zeros((n_loc, fanout, c), jnp.float32)
        bufs = [kf] + prim._ring_bufs(kf, send_local, P_)
        for k, buf in enumerate(bufs):
            qe = jnp.take(q, dst[k], axis=0).astype(jnp.float32)
            ke = jnp.take(buf, pos[k], axis=0).astype(jnp.float32)
            part = (qe * ke).reshape(-1, c, d_loc // c).sum(-1)
            attn = attn.at[dst[k], slot[k]].add(part * emask[k][:, None])
        return attn / jnp.sqrt(jnp.float32(d_loc // c))

    hd = P("data", "model")

    def prog(fn, w_spec, out_spec):
        return jax.jit(jax.shard_map(fn, mesh=mesh,
                                     in_specs=(hd, w_spec) + (plan,) * 5,
                                     out_specs=out_spec))
    return (prog(spmm, P("data", None), hd),
            prog(spmm, P("data", None, "model"), hd),
            prog(scores, hd, P("data", None, "model")))


def case_dense_vs_scatter(p, m, heads=4):
    """The slot-table SPMM (per-edge and head-major weights) and per-head
    attention against test-local copies of the edge-list scatter form
    they replaced (its scores through the same edge softmax), on one
    sampled layer.  Tolerance 1e-5 (values O(1-10)): the same f32
    products, summed over each row's slots in slot order instead of
    ring-group order, differ by a few ulps; a wrong row, slot or head
    errs by O(1)."""
    N, D, F = 256, 64, 8
    mesh = make_host_mesh(p, m)
    src, dst = rmat_edges(N, N * 8, seed=1)
    lg = sample_layer_graphs(csr_from_edges(src, dst, N), fanout=F,
                             n_layers=1, seed=0)[0]
    lp = build_plan([lg], p, m).layers[0]
    dev = prim.plan_device_arrays(lp)
    edges = [dev["send_local"]] + _edge_lists(lg, lp)
    rng = np.random.default_rng(31)
    put = lambda x, *spec: jax.device_put(  # noqa: E731
        jnp.asarray(x), NamedSharding(mesh, P(*spec)))
    H = put(rng.standard_normal((N, D), dtype=np.float32), "data", "model")
    q = put(rng.standard_normal((N, D), dtype=np.float32), "data", "model")
    w = put(mean_weights(lg.mask), "data", None)
    alpha = put(rng.random((N, F, heads), dtype=np.float32),
                "data", None, "model")
    deal = (put(lg.mask.astype(np.float32), "data", None),
            dev["send_local"], dev["slot_src"])
    spmm = prim.make_spmm_p(mesh, p)
    old_spmm, old_spmm_hm, old_scores = _scatter_programs(mesh, p, heads,
                                                          F)
    attn = prim.make_gat_attention_p(mesh, p, heads)
    mask = jnp.asarray(lg.mask)
    pairs = {"spmm": (spmm(H, w, *deal), old_spmm(H, w, *edges)),
             "spmm_head_major": (spmm(H, alpha, *deal),
                                 old_spmm_hm(H, alpha, *edges)),
             "attention": (attn(q, H, *deal),
                           edge_softmax(old_scores(q, H, *edges), mask))}
    errs = {k: float(np.abs(np.asarray(a) - np.asarray(b)).max())
            for k, (a, b) in pairs.items()}
    return max(errs.values()) <= 1e-5, " ".join(
        f"{k}={v:.2e}" for k, v in errs.items())


def case_delta_bitwise(mesh, heads):
    """The distributed delta refresh (``run_rows``, per-head attention
    on the row subset) commits the bits of a full epoch through the
    same executor."""
    import copy

    from repro.gnnserve import (DeltaReinference, MutationLog,
                                apply_edge_mutations, store_from_inference)
    src, dst = rmat_edges(256, 256 * 8, seed=1)
    g = csr_from_edges(src, dst, 256)
    lgs = sample_layer_graphs(g, fanout=8, n_layers=2, seed=0)
    rng = np.random.default_rng(29)
    X = rng.standard_normal((256, 64), dtype=np.float32)
    dex = DistExecutor(mesh)
    params = init_gat(jax.random.PRNGKey(6), [64, 64, 64], heads=heads)
    ri = DeltaReinference([copy.deepcopy(l) for l in lgs], "gat", params,
                          executor=dex)
    store = store_from_inference(X, ri.full_levels(X)[1:], n_shards=4)
    log = MutationLog()
    log.add_edges(rng.integers(0, 256, 8), rng.integers(0, 256, 8))
    fid = rng.choice(256, 3, replace=False)
    log.update_features(fid, rng.standard_normal((3, 64)).astype(np.float32))
    batch = log.drain()
    stats = ri.refresh(store, apply_edge_mutations(g, batch),
                       batch.feat_ids, batch.feat_rows,
                       batch.affected_dsts())
    X2 = X.copy()
    X2[batch.feat_ids] = batch.feat_rows
    oracle = DeltaReinference(ri.layer_graphs, "gat", params,
                              executor=dex).full_levels(X2)
    exact = all(bool((store.lookup(np.arange(256), lvl) ==
                      oracle[lvl]).all()) for lvl in (1, 2))
    partial = 0 < stats["frontier_sizes"][-1] < 256
    return exact and partial, (f"bitwise={exact} "
                               f"frontier={stats['frontier_sizes']}")


def case_plan_built_once():
    """With telemetry on, two ``infer_all`` epochs build the CommPlan
    once and give the same bits."""
    from repro.api import Session
    with Session.build(_gat_cfg(4, 2, 2, telemetry=True)) as s:
        H1 = np.array(s.infer_all())
        s._H = None
        H2 = np.array(s.infer_all())
        m = s.telemetry.metrics.to_dict()
    builds = m.get("dist.plan_builds")
    same = bool((H1 == H2).all())
    return (builds == 1 and same and
            m.get("dist.attn_heads_local") == 2.0), \
        f"plan_builds={builds} bitwise={same} " \
        f"heads_local={m.get('dist.attn_heads_local')}"


def case_slot_fill_reported():
    """``bind`` reports each layer's masked-in share of the slot table
    (the dense gather's real edges) as ``dist.slot_fill.layer<l>``."""
    from repro.api import Session
    with Session.build(_gat_cfg(4, 2, 2, telemetry=True)) as s:
        s.infer_all()
        m = s.telemetry.metrics.to_dict()
        want = [float(lg.mask.mean()) for lg in s.layer_graphs[:2]]
    got = [m.get(f"dist.slot_fill.layer{l}") for l in range(2)]
    return got == want and 0 < min(want) <= 1, f"got={got} want={want}"


def case_fetch_whole_rows(p, m):
    """``Session.infer_all`` copies the mesh's H in whole rows: its bits
    are those of ``run_model``'s sharded H (columns split over ``model``
    where m > 1, rows over ``data`` either way), the relayout runs once
    per epoch, and the relaid H is p*m blocks of whole rows."""
    from repro.api import Session
    with Session.build(_gat_cfg(4, p, m, telemetry=True)) as s:
        s.infer_all()
        s._H = None
        got = np.array(s.infer_all())
        ex = s.executor
        spec = model_spec("gat", s.params)
        H = run_model(ex, spec, ex.bind(s.layer_graphs, spec), s.X)
        mets = s.telemetry.metrics.to_dict()
    shard = H.sharding.shard_shape(H.shape)
    split = shard[0] < H.shape[0] and (shard[1] < H.shape[1]) == (m > 1)
    rows = ex._rows(H)
    whole = rows.sharding.shard_shape(rows.shape) == (
        H.shape[0] // (p * m), H.shape[1])
    same = bool((got == np.asarray(H)).all())
    relayouts = mets.get("dist.fetch_relayouts")
    return (same and split and whole and relayouts == 2), \
        f"bitwise={same} H_shard={shard} whole={whole} " \
        f"relayouts={relayouts}"


def gat_main():
    cases = [
        ("session_vs_ref/heads4_p2_m2",
         lambda: case_session_vs_ref(4, 2, 2)),
        ("session_vs_ref/heads2_p2_m2",
         lambda: case_session_vs_ref(2, 2, 2)),
        ("session_vs_ref/heads2_p2_m4",
         lambda: case_session_vs_ref(2, 2, 4)),
        ("heads1_bitwise_parent", case_heads1_bitwise_parent),
        ("delta_bitwise/heads4",
         lambda: case_delta_bitwise(make_host_mesh(4, 2), 4)),
        ("plan_built_once", case_plan_built_once),
        ("dense_vs_scatter/p2_m2", lambda: case_dense_vs_scatter(2, 2)),
        ("dense_vs_scatter/p4_m2", lambda: case_dense_vs_scatter(4, 2)),
        ("dense_vs_scatter/p2_m4", lambda: case_dense_vs_scatter(2, 4)),
        ("slot_fill_reported", case_slot_fill_reported),
        ("fetch_whole_rows/p2_m2", lambda: case_fetch_whole_rows(2, 2)),
        ("fetch_whole_rows/p2_m1", lambda: case_fetch_whole_rows(2, 1)),
    ]
    for name, fn in cases:
        hb.beat(name)
        try:
            ok, detail = fn()
        except Exception as exc:          # reported as this case's fail
            ok, detail = False, f"{type(exc).__name__}: {exc}"
        print(f"CASE {name} {'OK' if ok else 'FAIL'} {detail}", flush=True)
    print("GAT CASES DONE")


if __name__ == "__main__":
    if "--gat" in sys.argv:
        gat_main()
    else:
        main()
