"""The public API: DealConfig round-trip + validation, the plugin
registries, ExecutorSpec.build, and the deprecation shims' bitwise
equivalence to the pre-API hand-wired pipelines (ref + pallas)."""
import copy
import dataclasses
import pathlib

import numpy as np
import pytest

from repro.api import (ConfigError, DealConfig, ExecutorSpec, GraphSpec,
                       ModelSpec, PartitionSpec, QoSSpec, Session,
                       StoreSpec, register_evict_policy, register_model,
                       tenants_from_string)
from repro.api.registry import EVICT_POLICIES, MODELS

ROOT = pathlib.Path(__file__).resolve().parents[1]

SMALL = DealConfig(
    graph=GraphSpec(dataset="rmat", n_nodes=256, avg_degree=8, fanout=4),
    model=ModelSpec(name="gcn", n_layers=2, d_feature=16),
    qos=QoSSpec(staleness_bound=8))


# ----------------------------------------------------------------------
# config tree: serialization + validation
# ----------------------------------------------------------------------

def test_json_roundtrip_is_exact():
    cfgs = [
        DealConfig(),
        SMALL,
        DealConfig(
            graph=GraphSpec(dataset="ogbn-products", scale=0.5, seed=3),
            model=ModelSpec(name="gat", heads=2, d_feature=32),
            partition=PartitionSpec(p=4, m=2),
            executor=ExecutorSpec(name="pallas",
                                  options={"block_n": 8, "block_d": 64}),
            store=StoreSpec(budget_rows=128, evict_policy="lru",
                            admission="full", onboarding="tail"),
            qos=QoSSpec(staleness_bound=16, batch_slots=2,
                        tenants=tenants_from_string(
                            "ui:4:2:0:8,batch:1:1:64:512"))),
    ]
    for cfg in cfgs:
        assert DealConfig.from_json(cfg.to_json()) == cfg
        assert DealConfig.from_dict(cfg.to_dict()) == cfg
        # a second round trip is byte-stable too
        assert DealConfig.from_json(cfg.to_json()).to_json() \
            == cfg.to_json()


def test_checked_in_smoke_config_roundtrips():
    path = ROOT / "configs" / "examples" / "smoke.json"
    cfg = DealConfig.load(path).validate()
    assert DealConfig.from_json(cfg.to_json()) == cfg
    assert cfg.store.onboarding == "tail"


def test_validation_names_every_bad_field():
    bad = DealConfig(
        graph=GraphSpec(dataset="nope", scale=-1, fanout=0),
        model=ModelSpec(name="wat", n_layers=0, heads=3, d_feature=16),
        partition=PartitionSpec(p=0),
        executor=ExecutorSpec(name="cuda"),
        store=StoreSpec(n_shards=0, budget_rows=-2, evict_policy="bogus",
                        admission="maybe", onboarding="head"),
        qos=QoSSpec(staleness_bound=0,
                    tenants=({"name": "", "priority": -1},
                             {"name": "a"}, {"name": "a"})))
    with pytest.raises(ConfigError) as ei:
        bad.validate()
    msg = str(ei.value)
    for frag in ("graph.dataset", "graph.scale", "graph.fanout",
                 "model.name", "model.n_layers", "model.heads",
                 "partition.p", "executor.name", "store.n_shards",
                 "store.budget_rows", "store.evict_policy",
                 "store.admission", "store.onboarding",
                 "qos.staleness_bound", "qos.tenants[0].name",
                 "qos.tenants[0].priority", "qos.tenants[2].name"):
        assert frag in msg, f"{frag} missing from:\n{msg}"
    # unknown names list what IS registered
    assert "heat" in msg and "lru" in msg
    assert "gcn" in msg and "sage" in msg and "gat" in msg
    assert "ref" in msg and "pallas" in msg and "dist" in msg


@pytest.mark.parametrize("section,field,value", [
    ("store", "budget_mb", 3),
    # kernel and route choices are not config: the retired knobs are
    # rejected by name like any typo
    ("executor", "fused_gather", True),
    ("executor", "block_table", "default")])
def test_from_dict_rejects_unknown_fields_by_name(section, field, value):
    d = SMALL.to_dict()
    d[section][field] = value
    d["grph"] = {}
    with pytest.raises(ConfigError) as ei:
        DealConfig.from_dict(d)
    assert f"{section}.{field}" in str(ei.value)
    assert "grph" in str(ei.value)
    # a non-dict section is named too, not a raw TypeError
    with pytest.raises(ConfigError) as ei:
        DealConfig.from_json('{"graph": 5}')
    assert "graph" in str(ei.value)


def test_validation_names_wrong_typed_fields():
    # hand-edited JSON with wrong value types must get ConfigError with
    # the dotted field path, never a raw TypeError/ValueError
    with pytest.raises(ConfigError) as ei:
        DealConfig.from_json('{"graph": {"fanout": "8"}}').validate()
    assert "graph.fanout" in str(ei.value)
    with pytest.raises(ConfigError) as ei:
        DealConfig.from_json(
            '{"qos": {"tenants": ["ui:1:1:0:4"]}}').validate()
    assert "qos.tenants[0]" in str(ei.value)
    with pytest.raises(ConfigError) as ei:
        DealConfig.from_json('{"executor": {"options": 3}}').validate()
    assert "executor.options" in str(ei.value)
    # wrong-typed tenant FIELDS get dotted paths too
    with pytest.raises(ConfigError) as ei:
        DealConfig.from_json(
            '{"qos": {"tenants": [{"name": "ui", "priority": "4", '
            '"rate": "fast"}]}}').validate()
    assert "qos.tenants[0].priority" in str(ei.value)
    assert "qos.tenants[0].rate" in str(ei.value)
    # and the CLI parser reports ConfigError, not raw ValueError
    with pytest.raises(ConfigError):
        tenants_from_string("ui:abc:2:0:8")
    with pytest.raises(ConfigError):
        tenants_from_string("ui:1:1:0")         # wrong field count
    with pytest.raises(ConfigError):
        tenants_from_string("ui:-1:1:0:8")      # TenantSpec value check


def test_executor_spec_build_unknown_name_lists_registered():
    with pytest.raises(ConfigError) as ei:
        ExecutorSpec(name="cuda").build(PartitionSpec())
    msg = str(ei.value)
    assert "executor.name" in msg and "ref" in msg and "pallas" in msg


def test_executor_spec_dist_fallback_and_checks():
    from repro.core.ops import RefExecutor
    # trivial mesh falls back to ref (the old infer_gnn behavior) ...
    ex = ExecutorSpec(name="dist").build(PartitionSpec(p=1, m=1))
    assert isinstance(ex, RefExecutor)
    # ... unless the caller opted out of the fallback
    with pytest.raises(ConfigError):
        ExecutorSpec(name="dist", fallback_to_ref=False).build(
            PartitionSpec(p=64, m=64))   # no machine has 4096 devices


# ----------------------------------------------------------------------
# registries: third-party plugins without core edits
# ----------------------------------------------------------------------

def test_register_custom_evict_policy_runs_through_store():
    from repro.gnnserve import Query

    @register_evict_policy("fifo_test")
    def fifo(store, level):
        # evict the lowest shard id first, deterministically
        return lambda s: s
    try:
        cfg = dataclasses.replace(
            SMALL, store=StoreSpec(budget_rows=64,
                                   evict_policy="fifo_test"))
        eng = Session.build(cfg).serve()
        oracle = Session.build(SMALL).serve()
        ids = np.arange(256)
        q, qo = Query(uid=0, node_ids=ids), Query(uid=0, node_ids=ids)
        eng.submit(q), oracle.submit(qo)
        eng.run(), oracle.run()
        assert eng.store.n_evictions > 0, "budget never evicted"
        # recompute-on-miss keeps a custom policy bitwise-correct too
        assert np.array_equal(q.out, qo.out)
    finally:
        EVICT_POLICIES.unregister("fifo_test")
    with pytest.raises(ConfigError):
        cfg.validate()      # the name is gone again


def test_register_custom_model_runs_through_session():
    gcn = MODELS.get("gcn")
    register_model("gcn_custom_test", gcn)      # same math, new name
    try:
        cfg = dataclasses.replace(
            SMALL, model=dataclasses.replace(SMALL.model,
                                             name="gcn_custom_test"))
        H = Session.build(cfg).infer_all()
        H_ref = Session.build(SMALL).infer_all()
        assert np.array_equal(H, H_ref)
    finally:
        MODELS.unregister("gcn_custom_test")


def test_reregistering_builtin_requires_overwrite():
    with pytest.raises(ValueError):
        register_model("gcn", object())


# ----------------------------------------------------------------------
# shim equivalence: legacy entry points == the Session they delegate to
# ----------------------------------------------------------------------

SCALE = 256 / 8192          # ogbn-products at 256 nodes


def _legacy_infer(model, executor, *, p=2, m=1, fanout=4, n_layers=2,
                  d_feature=16, seed=0):
    """The pre-API body of launch/infer_gnn.run, verbatim wiring."""
    import jax

    from repro.core.gnn_models import init_gat, init_gcn, model_spec
    from repro.core.graph import csr_from_edges_distributed, make_dataset
    from repro.core.ops import get_executor, run_model
    from repro.core.sampler import sample_layer_graphs
    src, dst, n = make_dataset("ogbn-products", seed=seed, scale=SCALE)
    g, _ = csr_from_edges_distributed(src, dst, n, n_workers=p)
    lgs = sample_layer_graphs(g, fanout=fanout, n_layers=n_layers,
                              seed=seed)
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d_feature), dtype=np.float32)
    dims = [d_feature] * (n_layers + 1)
    key = jax.random.PRNGKey(seed)
    params = (init_gcn(key, dims) if model == "gcn"
              else init_gat(key, dims, heads=1))
    ex, spec = get_executor(executor), model_spec(model, params)
    return np.asarray(run_model(ex, spec, ex.bind(lgs, spec), X))


@pytest.mark.parametrize("executor", ["ref", "pallas"])
@pytest.mark.parametrize("model", ["gcn", "gat"])
def test_infer_gnn_shim_bitwise_equal(model, executor):
    from repro.launch.infer_gnn import run
    H = run("ogbn-products", model, p=2, m=1, fanout=4, n_layers=2,
            d_feature=16, executor=executor, distributed=False,
            scale=SCALE)
    np.testing.assert_array_equal(H, _legacy_infer(model, executor))


def _legacy_service(model, executor, *, fanout=4, n_layers=2,
                    d_feature=16, n_shards=4, staleness_bound=8, seed=0,
                    budget_rows=0):
    """The pre-API body of launch/serve_embeddings.build_service,
    verbatim wiring."""
    import jax

    from repro.core.gnn_models import init_gat, init_gcn, init_sage
    from repro.core.graph import csr_from_edges_distributed, make_dataset
    from repro.core.sampler import sample_layer_graphs
    from repro.gnnserve import (DeltaReinference, EmbeddingServeEngine,
                                attach_recompute, store_from_inference)
    src, dst, n = make_dataset("ogbn-products", seed=seed, scale=SCALE)
    g, _ = csr_from_edges_distributed(src, dst, n, n_workers=4)
    lgs = sample_layer_graphs(g, fanout=fanout, n_layers=n_layers,
                              seed=seed)
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d_feature), dtype=np.float32)
    key = jax.random.PRNGKey(seed)
    dims = [d_feature] * (n_layers + 1)
    params = {"gcn": lambda: init_gcn(key, dims),
              "sage": lambda: init_sage(key, dims),
              "gat": lambda: init_gat(key, dims, heads=1)}[model]()
    ri = DeltaReinference([copy.deepcopy(l) for l in lgs], model, params,
                          executor=executor)
    levels = ri.full_levels(X)
    store = store_from_inference(X, levels[1:], n_shards=n_shards,
                                 budget_rows=budget_rows or None)
    if budget_rows:
        attach_recompute(store, ri)
    return EmbeddingServeEngine(store, ri, g,
                                staleness_bound=staleness_bound)


def _drive_pair(eng_a, eng_b, n):
    """Identical traffic against both engines; returns the query pairs."""
    from repro.gnnserve import Query
    pairs = []
    for tick in range(4):
        rng = np.random.default_rng(100 + tick)
        ids = rng.integers(0, n, 32)
        qa, qb = Query(uid=tick, node_ids=ids), Query(uid=tick,
                                                      node_ids=ids)
        s_e, d_e = rng.integers(0, n, 4), rng.integers(0, n, 4)
        for eng, q in ((eng_a, qa), (eng_b, qb)):
            eng.submit(q)
            eng.mutate().add_edges(s_e, d_e)
            eng.run()
        pairs.append((qa, qb))
    return pairs


@pytest.mark.parametrize("executor", ["ref", "pallas"])
def test_build_service_shim_bitwise_equal(executor):
    from repro.launch.serve_embeddings import build_service
    eng = build_service("ogbn-products", "gcn", fanout=4, n_layers=2,
                        d_feature=16, staleness_bound=8,
                        executor=executor, scale=SCALE)
    legacy = _legacy_service("gcn", executor)
    n = eng.store.n_nodes
    assert n == legacy.store.n_nodes == 256
    for qa, qb in _drive_pair(eng, legacy, n):
        assert qa.done and qb.done
        assert qa.served_version == qb.served_version
        np.testing.assert_array_equal(qa.out, qb.out)
    assert eng.store.version == legacy.store.version


def test_budgeted_service_shim_bitwise_equal():
    from repro.launch.serve_embeddings import build_service
    eng = build_service("ogbn-products", "gcn", fanout=4, n_layers=2,
                        d_feature=16, staleness_bound=8,
                        budget_rows=96, scale=SCALE)
    legacy = _legacy_service("gcn", "ref", budget_rows=96)
    for qa, qb in _drive_pair(eng, legacy, eng.store.n_nodes):
        np.testing.assert_array_equal(qa.out, qb.out)
    assert eng.store.n_evictions > 0


# ----------------------------------------------------------------------
# one config drives offline AND online (the quickstart contract)
# ----------------------------------------------------------------------

def test_one_config_offline_and_online():
    with Session.build(SMALL) as s:
        H = s.infer_all()
        eng = s.serve()
        from repro.gnnserve import Query
        q = Query(uid=0, node_ids=np.arange(16))
        eng.submit(q)
        eng.run()
        # the served rows ARE the offline epoch's final level (the store
        # is built from the same layer graphs + params the epoch used)
        np.testing.assert_array_equal(q.out, H[:16])
        st = s.stats()
        assert st["n_served"] == 1 and "t_epoch_s" in st
    with pytest.raises(ConfigError):
        s.infer_all()       # closed
