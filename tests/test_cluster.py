"""Cluster serving tier (``gnnserve/cluster``): protocol framing, the
in-process WorkerCore WAL/seq contract, and the live 2-shard
deployment's headline guarantee — cluster-served lookups are BITWISE
equal to the single-process ``Session`` on the same ``DealConfig``
(ref + pallas), including after kill/restart/WAL-replay of one shard —
plus merged stats/attribution, heartbeat wedge detection, and the
aggregated ``/healthz``.

The deployment tests share module-scoped fixtures (worker processes
are expensive to spawn) and run in FILE ORDER: tests that mutate the
worlds mirror the mutation on BOTH the single-process and cluster
sessions, so the equal-worlds invariant holds for every later test.
"""
import json
import socket
import struct
import threading
import time
import urllib.request

import numpy as np
import pytest

from repro.api import (ClusterSpec, DealConfig, ExecutorSpec, GraphSpec,
                       ModelSpec, QoSSpec, Session, TelemetrySpec,
                       tenants_from_string)
from repro.gnnserve.cluster import (ProtocolError, WorkerCore,
                                    merge_health, recv_msg, send_msg)
from repro.gnnserve.engine import Query

N = 192
D = 16


def _cfg_dict(*, executor="ref", n=N):
    return {
        "graph": {"dataset": "rmat", "n_nodes": n, "avg_degree": 4,
                  "fanout": 4, "seed": 3},
        "model": {"name": "sage", "n_layers": 2, "d_feature": D},
        "executor": {"name": executor},
        "store": {"onboarding": "tail"},
        "qos": {"staleness_bound": 4},
    }


def _qos_cfg(*, n_shards=2, http_port=0):
    return DealConfig(
        graph=GraphSpec(dataset="rmat", n_nodes=N, avg_degree=4,
                        fanout=4, seed=3),
        model=ModelSpec(name="sage", n_layers=2, d_feature=D),
        executor=ExecutorSpec(name="ref"),
        qos=QoSSpec(staleness_bound=8, batch_slots=4, rows_per_step=64,
                    tenants=tenants_from_string(
                        "ui:4:2:0:4,batch:1:1:0:64")),
        telemetry=TelemetrySpec(enabled=True),
        cluster=ClusterSpec(n_shards=n_shards, http_port=http_port))


def _workload(eng, *, n=N, ticks=5, rows=12, seed=11):
    """Deterministic mixed traffic (edge adds + feature updates +
    queries) — identical on any engine built from the same config."""
    outs = []
    r = np.random.default_rng(seed)
    for t in range(ticks):
        log = eng.mutate()
        for _ in range(3):
            a, b = r.integers(0, n, 2)
            log.add_edge(int(a), int(b))
        ids = np.unique(r.integers(0, n, 4).astype(np.int64))
        log.update_features(
            ids, r.standard_normal((ids.size, D)).astype(np.float32))
        q = Query(1000 + t, r.integers(0, n, rows).astype(np.int64))
        eng.submit(q)
        eng.run()
        outs.append((q.out.copy(), q.served_version))
    return outs


# ----------------------------------------------------------------------
# protocol framing (no processes)
# ----------------------------------------------------------------------

def test_protocol_roundtrip_is_bit_exact():
    a, b = socket.socketpair()
    try:
        arrays = {
            "rows": np.random.default_rng(0).standard_normal(
                (7, 5)).astype(np.float32),
            "ids": np.arange(9, dtype=np.int64)[::3].copy(),
        }
        send_msg(a, {"op": "lookup", "level": -1, "ok": True}, arrays)
        header, got = recv_msg(b)
        assert header == {"op": "lookup", "level": -1, "ok": True}
        assert set(got) == {"rows", "ids"}
        for k in got:
            assert got[k].dtype == arrays[k].dtype
            assert np.array_equal(got[k], arrays[k])
        # empty-array legs survive too
        send_msg(b, {"op": "x"}, {"e": np.empty((0, 3), np.float32)})
        _, got = recv_msg(a)
        assert got["e"].shape == (0, 3)
    finally:
        a.close()
        b.close()


def test_protocol_rejects_eof_and_torn_frames():
    a, b = socket.socketpair()
    a.close()
    with pytest.raises(ProtocolError, match="closed"):
        recv_msg(b)
    b.close()
    a, b = socket.socketpair()
    try:
        # a frame whose header claims to be longer than the frame
        head = json.dumps({"op": "x"}).encode()
        body = struct.pack("<I", len(head) + 999) + head
        a.sendall(struct.pack("<I", len(body)) + body)
        with pytest.raises(ProtocolError, match="header length"):
            recv_msg(b)
    finally:
        a.close()
        b.close()


def test_protocol_frame_cap_rejects_allocation_bomb():
    # the cap must stay small enough that a corrupt length prefix can
    # never trigger a multi-GiB allocation in _recv_exact
    from repro.gnnserve.cluster.protocol import MAX_FRAME
    assert MAX_FRAME <= 1 << 28
    a, b = socket.socketpair()
    try:
        a.sendall(struct.pack("<I", MAX_FRAME + 1))
        with pytest.raises(ProtocolError, match="exceeds cap"):
            recv_msg(b)
    finally:
        a.close()
        b.close()


def test_float_wire_helpers_roundtrip_exactly():
    from repro.gnnserve.cluster.worker import (_rows_from_wire,
                                               _rows_to_wire)
    rows = np.random.default_rng(3).standard_normal(
        (11, 6)).astype(np.float32)
    wire = json.loads(json.dumps(_rows_to_wire(rows)))
    back = _rows_from_wire(wire)
    assert back.dtype == np.float32
    assert np.array_equal(back, rows)


# ----------------------------------------------------------------------
# WorkerCore in-process: seq chain, WAL replay, config neutralization
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def core_cfg():
    return DealConfig.from_dict({**_cfg_dict(n=128)})


def _commit_header(seq, edge_ops):
    return {"op": "commit", "seq": seq, "edge_ops": edge_ops,
            "n_new_nodes": 0}


def test_worker_core_seq_chain(core_cfg, tmp_path):
    core = WorkerCore(core_cfg, 0, 1, str(tmp_path))
    resp, _ = core.dispatch(_commit_header(1, [["add", 1, 2]]), {})
    assert resp["seq"] == 1 and not resp.get("duplicate")
    v1 = resp["store_version"]
    # duplicate seq acks idempotently, without re-applying
    resp, _ = core.dispatch(_commit_header(1, [["add", 1, 2]]), {})
    assert resp["duplicate"] and resp["store_version"] == v1
    # a gap breaks the monotonic chain loudly
    with pytest.raises(ValueError, match="monotonic"):
        core.dispatch(_commit_header(5, []), {})
    assert core.last_seq == 1


def test_worker_core_wal_replay_is_bitwise(core_cfg, tmp_path):
    import os
    run_dir = str(tmp_path)
    core = WorkerCore(core_cfg, 0, 1, run_dir)
    core.dispatch(_commit_header(1, [["add", 3, 4], ["add", 5, 6]]), {})
    core.dispatch(_commit_header(2, [["del", 3, 4]]), {})
    want, _ = core.dispatch({"op": "digest"}, {})
    # checkpoint restore path: ckpt has committed_seq == 2, empty replay
    restored = WorkerCore(core_cfg, 0, 1, run_dir)
    assert restored.restored and restored.last_seq == 2
    assert restored.replayed == 0
    got, _ = restored.dispatch({"op": "digest"}, {})
    assert got["digests"] == want["digests"]
    # full WAL replay path: no checkpoint, every entry replays
    os.unlink(core.ckpt_path)
    replayed = WorkerCore(core_cfg, 0, 1, run_dir)
    assert not replayed.restored and replayed.replayed == 2
    assert replayed.last_seq == 2
    got, _ = replayed.dispatch({"op": "digest"}, {})
    assert got["digests"] == want["digests"]
    assert got["store_version"] == want["store_version"]


def test_worker_rolls_back_wal_and_world_when_apply_fails(
        core_cfg, tmp_path, monkeypatch):
    (tmp_path / "w").mkdir()
    core = WorkerCore(core_cfg, 0, 1, str(tmp_path / "w"))
    core.dispatch(_commit_header(1, [["add", 1, 2]]), {})
    boom = {"on": True}
    real = WorkerCore._apply_commit

    def flaky(self, entry):
        if boom["on"]:
            raise RuntimeError("injected apply failure")
        return real(self, entry)

    monkeypatch.setattr(WorkerCore, "_apply_commit", flaky)
    with pytest.raises(RuntimeError, match="injected"):
        core.dispatch(_commit_header(2, [["add", 5, 6]]), {})
    # the torn seq-2 entry is truncated back out and the chain intact:
    # a restart must not replay it, a retry must not duplicate it
    assert core.last_seq == 1
    with open(core.wal_path) as f:
        lines = [l for l in f if l.strip()]
    assert len(lines) == 1 and json.loads(lines[0])["seq"] == 1
    boom["on"] = False
    resp, _ = core.dispatch(_commit_header(2, [["add", 5, 6]]), {})
    assert resp["seq"] == 2 and not resp["duplicate"]
    # ... and the recovered world is bitwise-equal to a never-failed one
    (tmp_path / "ctrl").mkdir()
    ctrl = WorkerCore(core_cfg, 0, 1, str(tmp_path / "ctrl"))
    ctrl.dispatch(_commit_header(1, [["add", 1, 2]]), {})
    ctrl.dispatch(_commit_header(2, [["add", 5, 6]]), {})
    assert core.dispatch({"op": "digest"}, {})[0]["digests"] == \
        ctrl.dispatch({"op": "digest"}, {})[0]["digests"]


def test_replay_rejects_duplicate_and_gapped_wal(core_cfg, tmp_path):
    entry = {"seq": 1, "kind": "commit", "edge_ops": [["add", 1, 2]],
             "feat_ids": [], "feat_rows": [], "n_new_nodes": 0,
             "new_node_rows": None}
    dup = tmp_path / "dup"
    dup.mkdir()
    (dup / "shard0.wal").write_text(
        json.dumps(entry) + "\n" + json.dumps(entry) + "\n")
    with pytest.raises(ValueError, match="duplicate|out-of-order"):
        WorkerCore(core_cfg, 0, 1, str(dup))
    gap = tmp_path / "gap"
    gap.mkdir()
    (gap / "shard0.wal").write_text(
        json.dumps(entry) + "\n" + json.dumps({**entry, "seq": 3})
        + "\n")
    with pytest.raises(ValueError, match="gap"):
        WorkerCore(core_cfg, 0, 1, str(gap))


def test_worker_config_overrides_and_neutralization(tmp_path):
    cfg = DealConfig.from_dict({
        **_cfg_dict(n=128),
        "telemetry": {"enabled": False, "http_port": 9999},
        "cluster": {"n_shards": 2,
                    "overrides": [{"shard": 1, "budget_rows": 64,
                                   "staleness_bound": 2}]},
    })
    core = WorkerCore(cfg, 1, 2, str(tmp_path))
    assert core.cfg.cluster.n_shards == 0      # no recursive clusters
    assert core.cfg.telemetry.http_port == -1  # router owns the door
    assert core.cfg.store.budget_rows == 64
    assert core.cfg.qos.staleness_bound == 2
    (tmp_path / "s0").mkdir()
    other = WorkerCore(cfg, 0, 2, str(tmp_path / "s0"))
    assert other.cfg.store.budget_rows == 0    # override is shard-1 only


# ----------------------------------------------------------------------
# router failure semantics over in-process cores (no sockets)
# ----------------------------------------------------------------------

class _CoreChannel:
    """In-process stand-in for ``protocol.Channel`` over a WorkerCore:
    the same request/close surface and error taxonomy (WorkerError for
    handler failures), plus fault injection — ops named in ``fail_ops``
    raise OSError BEFORE reaching the core, modelling a transport
    failure where the shard never saw the RPC."""

    def __init__(self, core):
        self.core = core
        self.fail_ops = set()
        self._lock = threading.Lock()

    def request(self, op, arrays=None, **fields):
        from repro.gnnserve.cluster import WorkerError
        with self._lock:
            if op in self.fail_ops:
                raise OSError(f"injected transport failure on {op!r}")
            try:
                return self.core.dispatch({"op": op, **fields},
                                          dict(arrays or {}))
            except Exception as exc:
                raise WorkerError(
                    f"shard op {op!r} failed: {exc}") from exc

    def close(self):
        pass


@pytest.fixture()
def core_router(core_cfg, tmp_path):
    from repro.gnnserve.cluster import Router
    cores, channels = [], []
    for s in range(2):
        d = tmp_path / f"shard{s}"
        d.mkdir()
        core = WorkerCore(core_cfg, s, 2, str(d))
        cores.append(core)
        channels.append(_CoreChannel(core))
    st, _ = cores[0].dispatch({"op": "status"}, {})
    bounds = np.linspace(0, st["n_nodes"], 3).astype(np.int64)
    return Router(channels, bounds, st["dims"]), cores, channels


def _core_digests(cores):
    return [c.dispatch({"op": "digest"}, {})[0]["digests"]
            for c in cores]


def test_commit_requeues_when_durable_nowhere(core_router):
    router, cores, channels = core_router
    for ch in channels:
        ch.fail_ops.add("commit")
    router.log.add_edge(1, 2)
    with pytest.raises(RuntimeError, match="requeued"):
        router.commit_pending()
    # nothing applied anywhere, the batch is back in the log, and no
    # shard's seq moved — the next commit re-drains under fresh seqs
    assert router.log.pending == 1
    assert router.seq == [0, 0]
    assert all(c.last_seq == 0 for c in cores)
    for ch in channels:
        ch.fail_ops.clear()
    router.commit_pending()
    assert router.seq == [1, 1]
    assert router.log.pending == 0
    d0, d1 = _core_digests(cores)
    assert d0 == d1


def test_commit_partial_failure_parks_inflight_no_seq_reuse(
        core_router, core_cfg, tmp_path):
    router, cores, channels = core_router
    channels[1].fail_ops.add("commit")
    router.log.add_edge(3, 4)
    with pytest.raises(RuntimeError, match="in-flight"):
        router.commit_pending()
    # shard 0 folded the batch; it must NOT requeue (that would double-
    # apply on shard 0 under a reused seq) — it parks in-flight instead
    assert router.seq == [1, 0]
    assert router.log.pending == 0
    assert router.router_stats()["inflight"] == "commit"
    # a new mutation arrives while the commit is parked
    router.log.add_edge(5, 6)
    channels[1].fail_ops.clear()
    router.commit_pending()     # drives the parked batch, then drains
    assert router.seq == [2, 2]
    assert router.router_stats()["inflight"] is None
    d0, d1 = _core_digests(cores)
    assert d0 == d1
    # no double-apply anywhere: equal to a control fed each batch once
    (tmp_path / "ctrl").mkdir()
    ctrl = WorkerCore(core_cfg, 0, 1, str(tmp_path / "ctrl"))
    ctrl.dispatch(_commit_header(1, [["add", 3, 4]]), {})
    ctrl.dispatch(_commit_header(2, [["add", 5, 6]]), {})
    assert ctrl.dispatch({"op": "digest"}, {})[0]["digests"] == d0


def test_commit_resyncs_seq_when_only_the_ack_is_lost(core_router):
    """An applied-but-unacked commit must advance the router's seq via
    the status resync — NOT be re-sent as a new batch (the duplicate
    ack path) or requeued (double-apply)."""
    from repro.gnnserve.cluster import WorkerError
    router, cores, channels = core_router
    real = channels[1].request

    def drop_ack(op, arrays=None, **fields):
        resp = real(op, arrays, **fields)
        if op == "commit":
            raise WorkerError("injected ack loss after apply")
        return resp

    channels[1].request = drop_ack
    router.log.add_edge(7, 8)
    router.commit_pending()     # resync sees last_seq==target: no error
    channels[1].request = real
    assert router.seq == [1, 1]
    assert all(c.last_seq == 1 for c in cores)
    assert router.log.pending == 0
    d0, d1 = _core_digests(cores)
    assert d0 == d1


def test_concurrent_lookups_and_scrapes_never_tear_a_commit(
        core_router):
    router, cores, _ = core_router
    errs = []
    stop = threading.Event()

    def _reader(seed):
        r = np.random.default_rng(seed)
        while not stop.is_set():
            try:
                rows, _ = router.lookup(
                    r.integers(0, 128, 8).astype(np.int64))
                assert rows.shape == (8, D)
                router.engine_stats()   # merged scrape mid-commit
            except Exception as exc:    # noqa: BLE001 — recorded
                errs.append(exc)
                return

    threads = [threading.Thread(target=_reader, args=(i,), daemon=True)
               for i in range(3)]
    for t in threads:
        t.start()
    for i in range(6):
        router.log.add_edge(int(i), int((i * 7 + 1) % 128))
        router.commit_pending()
    stop.set()
    for t in threads:
        t.join(timeout=30)
    assert not errs, f"reader raced a commit: {errs[0]}"
    d0, d1 = _core_digests(cores)
    assert d0 == d1


def test_lookup_empty_ids_returns_empty_rows(core_router):
    router, _, _ = core_router
    rows, version = router.lookup(np.empty(0, np.int64))
    assert rows.shape == (0, D)
    assert rows.dtype == np.float32
    assert version == router.statuses()[0]["store_version"]


# ----------------------------------------------------------------------
# the live 2-shard deployment vs the single-process Session
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def fifo_pair():
    base = _cfg_dict()
    s1 = Session.build(DealConfig.from_dict(base))
    e1 = s1.serve()
    s2 = Session.build(DealConfig.from_dict(
        {**base, "cluster": {"n_shards": 2}}))
    e2 = s2.serve()
    o1 = _workload(e1)
    o2 = _workload(e2)
    yield s1, e1, o1, s2, e2, o2
    s1.close()
    s2.close()


def test_cluster_serves_bitwise_equal_to_single_process(fifo_pair):
    _, _, o1, _, _, o2 = fifo_pair
    for i, ((rows1, v1), (rows2, v2)) in enumerate(zip(o1, o2)):
        assert v1 == v2, f"tick {i}: served versions diverge"
        assert np.array_equal(rows1, rows2), f"tick {i}: bytes diverge"


def test_shards_hold_identical_worlds(fifo_pair):
    *_, s2, _, _ = fifo_pair
    digs = s2.cluster.router.digests()
    assert digs[0]["digests"] == digs[1]["digests"]
    assert digs[0]["store_version"] == digs[1]["store_version"]
    sts = s2.cluster.router.statuses()
    assert [st["shard"] for st in sts] == [0, 1]
    assert all(st["pending"] == 0 for st in sts)


def test_merged_stats_keep_session_schema(fifo_pair):
    s1, _, o1, s2, _, _ = fifo_pair
    st1, st2 = s1.stats(), s2.stats()
    # the cluster tree is a superset of the single-process one
    missing = set(st1) - set(st2)
    assert not missing, f"merged stats dropped keys: {sorted(missing)}"
    assert st2["store_version"] == st1["store_version"]
    assert st2["n_served"] == len(o1)          # client queries, not RPCs
    assert st2["n_served_subqueries"] >= st2["n_served"]
    assert st2["pending_mutations"] == 0
    cl = st2["cluster"]
    assert cl["n_shards"] == 2 and len(cl["shards"]) == 2
    assert cl["router"]["n_lookups"] == len(o1)
    assert cl["router"]["seq"] == [5, 5]       # one commit per tick


def test_full_epoch_matches_single_process(fifo_pair):
    _, e1, _, s2, e2, _ = fifo_pair
    e1.full_epoch()
    e2.full_epoch()
    digs = s2.cluster.router.digests()
    assert digs[0]["digests"] == digs[1]["digests"]
    r = np.random.default_rng(23)
    ids = r.integers(0, N, 16).astype(np.int64)
    q1, q2 = Query(2000, ids), Query(2000, ids.copy())
    e1.submit(q1), e2.submit(q2)
    e1.run(), e2.run()
    assert q1.served_version == q2.served_version
    assert np.array_equal(q1.out, q2.out)


def test_killed_shard_rejoins_bitwise_after_replay(fifo_pair):
    _, e1, _, s2, e2, _ = fifo_pair
    dep = s2.cluster
    dep.kill_worker(1)
    dep.restart_worker(1)
    digs = dep.router.digests()
    assert digs[0]["digests"] == digs[1]["digests"], \
        "restarted shard is not bitwise-equal after checkpoint+replay"
    sts = dep.router.statuses()
    assert sts[1]["restored"]                   # came back via checkpoint
    ids = np.arange(60, 120, dtype=np.int64)    # spans both shards
    q1, q2 = Query(3000, ids), Query(3000, ids.copy())
    e1.submit(q1), e2.submit(q2)
    e1.run(), e2.run()
    assert np.array_equal(q1.out, q2.out)
    assert dep.n_restarts >= 1


def test_router_retries_transparently_through_a_dead_worker(fifo_pair):
    _, e1, _, s2, e2, _ = fifo_pair
    dep = s2.cluster
    before = dep.router.n_retries
    dep.kill_worker(0)                          # kill, do NOT restart
    ids = np.arange(0, 50, dtype=np.int64)      # owned by shard 0
    q1, q2 = Query(4000, ids), Query(4000, ids.copy())
    e1.submit(q1), e2.submit(q2)
    e1.run(), e2.run()                          # reconnect hook respawns
    assert np.array_equal(q1.out, q2.out)
    assert dep.router.n_retries > before


def test_wedged_worker_killed_with_stage_named_diagnosis(fifo_pair):
    *_, s2, _, _ = fifo_pair
    dep = s2.cluster
    hbs = dep.check_heartbeats()
    assert all(h["alive"] and h["age_s"] < 5.0 for h in hbs)

    def _hang():
        try:
            dep.router.channels[1].request("_test_hang", seconds=60)
        except Exception:
            pass                                # killed mid-request

    t = threading.Thread(target=_hang, daemon=True)
    t.start()
    deadline = time.time() + 15.0
    while time.time() < deadline:
        hbs = dep.check_heartbeats()
        if hbs[1]["stage"] == "op:_test_hang" and hbs[1]["age_s"] > 1.0:
            break
        time.sleep(0.2)
    diags = dep.kill_wedged(max_age_s=1.0, restart=True)
    t.join(timeout=10)
    assert len(diags) == 1
    assert "shard 1" in diags[0] and "op:_test_hang" in diags[0]
    digs = dep.router.digests()                 # rejoined bitwise again
    assert digs[0]["digests"] == digs[1]["digests"]


def test_node_adds_route_and_onboard_identically(fifo_pair):
    _, e1, _, s2, e2, _ = fifo_pair
    n0 = e2.store.n_nodes
    for eng in (e1, e2):
        r = np.random.default_rng(31)
        log = eng.mutate()
        log.add_nodes(3, r.standard_normal((3, D)).astype(np.float32))
        log.add_edge(int(n0), 5)
        log.add_edge(7, int(n0 + 2))
        eng.refresh()
    assert e1.store.n_nodes == e2.store.n_nodes == n0 + 3
    ids = np.arange(n0 - 2, n0 + 3, dtype=np.int64)   # tail straddle
    q1, q2 = Query(5000, ids), Query(5000, ids.copy())
    e1.submit(q1), e2.submit(q2)
    e1.run(), e2.run()
    assert np.array_equal(q1.out, q2.out)
    digs = s2.cluster.router.digests()
    assert digs[0]["digests"] == digs[1]["digests"]


@pytest.mark.parametrize("executor", ["pallas"])
def test_cluster_bitwise_on_accelerated_executor(executor):
    base = _cfg_dict(executor=executor, n=128)
    with Session.build(DealConfig.from_dict(base)) as s1, \
            Session.build(DealConfig.from_dict(
                {**base, "cluster": {"n_shards": 2}})) as s2:
        o1 = _workload(s1.serve(), n=128, ticks=3)
        o2 = _workload(s2.serve(), n=128, ticks=3)
        for (rows1, v1), (rows2, v2) in zip(o1, o2):
            assert v1 == v2
            assert np.array_equal(rows1, rows2)
        s2.cluster.kill_worker(0)
        s2.cluster.restart_worker(0)
        digs = s2.cluster.router.digests()
        assert digs[0]["digests"] == digs[1]["digests"]


def test_cluster_refused_on_chip_host(monkeypatch, tmp_path):
    """Where this process holds chips, a cluster config is refused
    before any worker spawns, naming cluster.n_shards: no worker may
    fall back to the CPU."""
    import jax

    from repro.api import ConfigError
    from repro.gnnserve.cluster import ClusterDeployment
    cfg = DealConfig.from_dict({**_cfg_dict(n=128),
                                "cluster": {"n_shards": 2}})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ConfigError, match=r"cluster\.n_shards"):
        ClusterDeployment(cfg, run_dir=str(tmp_path))
    assert list(tmp_path.iterdir()) == []       # nothing spawned


# ----------------------------------------------------------------------
# QoS + telemetry cluster: merged attribution, aggregated /healthz
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def qos_cluster():
    s = Session.build(_qos_cfg())
    eng = s.serve()
    r = np.random.default_rng(5)
    for t in range(12):
        for tenant, rows in (("ui", 4), ("batch", 24)):
            ids = r.integers(0, N, rows).astype(np.int64)
            eng.submit(Query(100 * t + rows, ids, tenant=tenant))
        log = eng.mutate()
        log.add_edge(int(r.integers(0, N)), int(r.integers(0, N)))
        eng.run()
    yield s, eng
    s.close()


def test_cluster_attribution_reconciles_within_gate(qos_cluster):
    from repro.obs.report import ATTRIBUTION_TOLERANCE
    s, _ = qos_cluster
    st = s.stats()
    attribution = st.get("attribution", {})
    assert set(attribution) == {"ui", "batch"}
    for tenant, doc in attribution.items():
        assert doc["n_queries"] > 0
        frac = doc["attributed_frac"]
        assert abs(frac - 1.0) <= ATTRIBUTION_TOLERANCE, \
            f"tenant {tenant}: merged attribution closes at {frac:.3f}"
    tenants = st["tenants"]
    assert set(tenants) == {"ui", "batch"}
    assert tenants["ui"]["staleness_slo"] == 4


def test_router_healthz_aggregates_per_shard_health(qos_cluster):
    s, _ = qos_cluster
    ep = s.cluster.endpoint
    assert ep is not None and ep.port
    base = f"http://127.0.0.1:{ep.port}"
    with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
        doc = json.loads(r.read())
    assert doc["status"] in ("ok", "alerting")
    assert [sh["shard"] for sh in doc["shards"]] == [0, 1]
    for sh in doc["shards"]:
        assert sh["status"] in ("ok", "alerting")
    with urllib.request.urlopen(f"{base}/shards", timeout=10) as r:
        shards = json.loads(r.read())
    assert shards["router"]["n_lookups"] > 0
    assert len(shards["shards"]) == 2
    with urllib.request.urlopen(f"{base}/stats", timeout=10) as r:
        st = json.loads(r.read())
    assert st["cluster"]["n_shards"] == 2


def test_merge_health_fires_if_any_shard_fires():
    ok = {"n_alerts": 0, "alerts": [], "burn_rate": {"ui": 0.1},
          "wait_burn_rate": {}, "firing": []}
    bad = {"n_alerts": 2,
           "alerts": [{"kind": "slo_burn", "tenant": "ui"}],
           "burn_rate": {"ui": 2.5}, "wait_burn_rate": {},
           "firing": ["slo_burn:ui"]}
    merged = merge_health([ok, bad])
    assert merged["status"] == "alerting"
    assert merged["firing"] == ["shard1:slo_burn:ui"]
    assert merged["burn_rate"]["ui"] == 2.5    # worst shard wins
    assert merged["alerts"][0]["shard"] == 1
    assert [s["status"] for s in merged["shards"]] == ["ok", "alerting"]
    assert merge_health([ok, ok])["status"] == "ok"
