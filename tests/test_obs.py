"""The obs layer: deterministic-clock span nesting/ordering, the no-op
zero-allocation guarantee, exporter golden files, the QoS stats key-drift
guard, trace validation, and the bitwise proof that instrumentation never
changes inference outputs (ref + pallas)."""
import json
import sys

import numpy as np
import pytest

from repro import obs
from repro.api import DealConfig, GraphSpec, ModelSpec, QoSSpec, Session
from repro.obs import compat
from repro.obs.validate import validate_trace


def _tel():
    return obs.Telemetry(enabled=True, clock=obs.FakeClock(0, 1000))


# ----------------------------------------------------------------------
# spans: nesting, ordering, deterministic clock
# ----------------------------------------------------------------------

def test_span_records_name_duration_depth():
    tel = _tel()
    with tel.span("a"):
        pass
    # FakeClock(step=1000): enter + exit = 2 reads -> dur 1000ns
    (name, t0, dur, depth, attrs), = tel.tracer.events
    assert (name, t0, dur, depth, attrs) == ("a", 0, 1000, 0, None)


def test_span_nesting_depths_and_order():
    tel = _tel()
    with tel.span("outer"):
        with tel.span("inner1"):
            pass
        with tel.span("inner2") as sp:
            sp.set(rows=7)
    # recorded at EXIT: children first, parent last
    names = [e[0] for e in tel.tracer.events_in_order()]
    assert names == ["inner1", "inner2", "outer"]
    depths = {e[0]: e[3] for e in tel.tracer.events}
    assert depths == {"outer": 0, "inner1": 1, "inner2": 1}
    attrs = {e[0]: e[4] for e in tel.tracer.events}
    assert attrs["inner2"] == {"rows": 7}
    # parent's interval contains the children's
    ev = {e[0]: e for e in tel.tracer.events}
    for child in ("inner1", "inner2"):
        assert ev["outer"][1] <= ev[child][1]
        assert (ev[child][1] + ev[child][2]
                <= ev["outer"][1] + ev["outer"][2])


def test_span_ring_buffer_drops_oldest():
    tel = obs.Telemetry(enabled=True, clock=obs.FakeClock(0, 1000),
                        capacity=3)
    for i in range(5):
        with tel.span(f"s{i}"):
            pass
    assert tel.tracer.n_dropped == 2
    assert [e[0] for e in tel.tracer.events_in_order()] == \
        ["s2", "s3", "s4"]


def test_span_feeds_duration_histogram_with_executor_attribution():
    # one histogram per span name: a session runs one executor, so the
    # ``executor`` attr adds no second, attributed series
    tel = _tel()
    with tel.span("ops.spmm") as sp:
        sp.set(executor="pallas")
    d = tel.metrics.to_dict()
    assert d["ops.spmm_ms.count"] == 1
    assert d["ops.spmm_ms.sum"] == pytest.approx(1e-3)   # 1000ns
    assert {k.rsplit(".", 1)[0] for k in d} == {"ops.spmm_ms"}


def test_coverage_interval_union():
    tel = _tel()
    clk = tel.tracer.clock
    with tel.span("a"):        # [0, 1000]
        pass
    clk.advance(8000)          # gap [2000, 10000]
    with tel.span("b"):        # [10000, 11000]
        pass
    lo, hi = tel.tracer.window_ns()
    assert (lo, hi) == (0, 11000)
    assert tel.tracer.covered_ns() == 2000
    assert tel.tracer.coverage() == pytest.approx(2000 / 11000)


def test_use_scopes_and_restores():
    tel = _tel()
    assert not obs.enabled()
    with obs.use(tel):
        assert obs.enabled() and obs.current() is tel
        with obs.span("x"):
            pass
        obs.add("c", 2)
    assert not obs.enabled()
    assert [e[0] for e in tel.tracer.events] == ["x"]
    assert tel.metrics.counter("c").value == 2


# ----------------------------------------------------------------------
# no-op mode: falsy spans, zero allocation
# ----------------------------------------------------------------------

def test_disabled_span_is_shared_falsy_noop():
    assert obs.span("anything") is obs.NOOP_SPAN
    assert not obs.NOOP_SPAN
    with obs.span("anything") as sp:
        assert sp is obs.NOOP_SPAN
        sp.set(rows=1)          # swallowed


def test_disabled_hot_path_allocates_nothing():
    def hot():
        with obs.span("x") as sp:
            if sp:
                sp.set(rows=1)
        obs.add("c")
        obs.observe("h", 1.0)
        obs.gauge("g", 2.0)

    hot()                       # warm any lazy interpreter state
    deltas = []
    for _ in range(5):
        before = sys.getallocatedblocks()
        hot()
        deltas.append(sys.getallocatedblocks() - before)
    # min over trials: unrelated interpreter churn can add blocks in
    # some trials, but a true no-op must manage zero in at least one
    assert min(deltas) <= 0


# ----------------------------------------------------------------------
# exporters: golden files under the deterministic clock
# ----------------------------------------------------------------------

def _golden_tel():
    tel = _tel()
    with tel.span("serve.step"):
        with tel.span("store.gather") as sp:
            sp.set(rows=4, level=1)
    tel.add("store.evictions", 2)
    tel.observe("serve.queue_wait_ms", 1.5)
    tel.observe("serve.queue_wait_ms", 2.5)
    return tel


def test_chrome_trace_golden(tmp_path):
    tel = _golden_tel()
    doc = obs.dump_chrome_trace(tel.tracer, tmp_path / "t.json",
                                tel.metrics, process_name="deal.test")
    assert doc == json.loads((tmp_path / "t.json").read_text())
    assert doc["displayTimeUnit"] == "ms"
    meta, gather, step = doc["traceEvents"]
    assert meta == {"name": "process_name", "ph": "M", "pid": 0,
                    "tid": 0, "args": {"name": "deal.test"}}
    # clock reads: step-enter(0) gather-enter(1000) gather-exit(2000)
    # step-exit(3000); ts/dur in us
    assert gather == {"name": "store.gather", "cat": "store", "ph": "X",
                      "ts": 1.0, "dur": 1.0, "pid": 0, "tid": 0,
                      "args": {"rows": 4, "level": 1, "depth": 1}}
    assert step == {"name": "serve.step", "cat": "serve", "ph": "X",
                    "ts": 0.0, "dur": 3.0, "pid": 0, "tid": 0,
                    "args": {"depth": 0}}
    assert doc["deal_metrics"]["store.evictions"] == 2
    assert doc["deal_metrics"]["serve.queue_wait_ms.count"] == 2


def test_prometheus_text_golden():
    tel = _golden_tel()
    text = obs.prometheus_text(tel.metrics)
    assert "# TYPE deal_store_evictions counter\n" \
           "deal_store_evictions 2" in text
    assert "# TYPE deal_serve_queue_wait_ms summary" in text
    assert 'deal_serve_queue_wait_ms{quantile="0.5"} 1.5' in text
    assert 'deal_serve_queue_wait_ms{quantile="0.95"} 2.5' in text
    assert "deal_serve_queue_wait_ms_sum 4" in text
    assert "deal_serve_queue_wait_ms_count 2" in text
    # span-derived histograms ride along, dots sanitized
    assert "deal_serve_step_ms_count 1" in text


def test_metrics_registry_strict_typing():
    tel = _tel()
    tel.add("x", 1)
    with pytest.raises(TypeError, match="counter"):
        tel.metrics.histogram("x")


# ----------------------------------------------------------------------
# trace validation (the CI smoke gate)
# ----------------------------------------------------------------------

def test_validate_trace_accepts_golden():
    tel = _golden_tel()
    doc = obs.chrome_trace(tel.tracer, tel.metrics)
    problems, summary = validate_trace(doc, min_coverage=0.9,
                                       require_cats=("serve", "store"))
    assert problems == []
    assert summary["n_spans"] == 2
    assert summary["coverage"] == pytest.approx(1.0)


def test_validate_trace_rejects_bad_docs():
    assert validate_trace({"traceEvents": "nope"})[0]
    bad_event = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": -1, "dur": 2,
         "pid": 0, "tid": 0}]}
    assert any("ts" in p for p in validate_trace(bad_event)[0])
    missing_cat = obs.chrome_trace(_golden_tel().tracer)
    problems, _ = validate_trace(missing_cat, require_cats=("ops",))
    assert any("ops" in p for p in problems)


# ----------------------------------------------------------------------
# stats unification: compat aliases + the QoS key-drift guard
# ----------------------------------------------------------------------

def test_qos_stats_contract_matches_consumers():
    """bench_qos.py and serve_embeddings.drive read these tenant fields
    — QoSScheduler.stats() must keep emitting every one (this is the
    key-drift guard), and the compat map must translate each."""
    from repro.gnnserve.qos import (QoSScheduler, TenantRegistry,
                                    TenantSpec)
    reg = TenantRegistry([TenantSpec(name="t0", priority=1.0,
                                     slot_quota=1, rate=0,
                                     staleness_slo=8)])
    stats = QoSScheduler(reg, batch_slots=2, rows_per_step=8).stats()
    tenant = stats["t0"]
    missing = compat.TENANT_CONSUMED_FIELDS - set(tenant)
    assert not missing, f"QoS stats dropped consumed keys: {missing}"
    untranslated = set(tenant) - set(compat.TENANT_MAP.values())
    assert not untranslated, \
        f"tenant stats keys missing a unified alias: {untranslated}"


def test_unified_from_engine_translates_all_shapes():
    engine_stats = {"n_served": 3, "n_gather_steps": 5,
                    "store_n_evictions": 2, "store_hits": 10,
                    "store_recompute_s": 0.25,
                    "tenants": {"batch": {"wait_p95_steps": 4.0,
                                          "n_preemptions": 1}}}
    uni = compat.unified_from_engine(engine_stats)
    assert uni["serve.queries"] == 3
    assert uni["store.evictions"] == 2
    assert uni["store.recompute_ms"] == pytest.approx(250.0)
    assert uni["qos.tenant.batch.p95_wait_steps"] == 4.0
    assert uni["qos.tenant.batch.preemptions"] == 1


# ----------------------------------------------------------------------
# end-to-end: Session telemetry + the bitwise neutrality proof
# ----------------------------------------------------------------------

def _small_cfg(executor="ref", telemetry=False):
    cfg = DealConfig(
        graph=GraphSpec(dataset="rmat", n_nodes=256, avg_degree=8,
                        fanout=4),
        model=ModelSpec(name="gcn", n_layers=2, d_feature=16),
        qos=QoSSpec(staleness_bound=8))
    cfg.executor.name = executor
    cfg.telemetry.enabled = telemetry
    return cfg


@pytest.mark.parametrize("executor", ["ref", "pallas"])
def test_instrumentation_is_bitwise_neutral(executor):
    with Session.build(_small_cfg(executor)) as off:
        H_off = off.infer_all().copy()
    with Session.build(_small_cfg(executor, telemetry=True)) as on:
        H_on = on.infer_all().copy()
        assert len(on.telemetry.tracer.events) > 0
    assert H_off.dtype == H_on.dtype
    assert np.array_equal(H_off, H_on)      # bitwise, not approx


def test_session_stats_surfaces_plan_cache_and_frontiers():
    with Session.build(_small_cfg(telemetry=True)) as s:
        s.serve()
        s.apply_mutations().add_edges(np.array([1, 2]), np.array([3, 4]))
        s.refresh()
        st = s.stats()
    assert {"hits", "misses"} <= set(st["plan_cache"])
    m = st["metrics"]
    assert "plan_cache.hits" in m and "plan_cache.misses" in m
    assert "delta.frontier_rows.layer0" in m
    assert m["serve.refreshes"] == 1
    # live telemetry histograms merged on top of the derived aliases
    assert m["refresh.layer_ms.count"] >= 1


def test_session_dump_trace_is_valid_and_covering(tmp_path):
    """Spans cover the traced window.  On the fake clock every clock
    read is one step, so coverage counts the pipeline's phases that sit
    outside every span, not host time: a warm process runs this window
    in ~15 ms, where one host stall between spans weighs tens of
    percent."""
    cfg = _small_cfg(telemetry=True)
    cfg.telemetry.clock = "fake"
    with Session.build(cfg) as s:
        s.infer_all()
        s.serve()
        doc = s.dump_trace(tmp_path / "trace.json")
        assert s.prometheus_text().startswith("# TYPE")
    problems, summary = validate_trace(
        doc, min_coverage=0.9,
        require_cats=("construct", "sample", "featprep", "ops", "serve"))
    assert problems == []
    assert summary["coverage"] >= 0.9


def test_dump_trace_without_telemetry_raises():
    from repro.api import ConfigError
    with Session.build(_small_cfg()) as s:
        assert s.telemetry is None
        with pytest.raises(ConfigError, match="telemetry"):
            s.dump_trace("/tmp/never.json")
        assert s.prometheus_text() == ""


def test_session_installs_and_restores_current_telemetry():
    assert obs.current() is obs.DISABLED
    with Session.build(_small_cfg(telemetry=True)) as s:
        assert obs.current() is s.telemetry
    assert obs.current() is obs.DISABLED


def test_telemetry_spec_roundtrip_and_validation():
    from repro.api import ConfigError
    cfg = _small_cfg(telemetry=True)
    cfg.telemetry.clock = "fake"
    cfg2 = DealConfig.from_json(cfg.to_json())
    assert cfg2.telemetry == cfg.telemetry
    tel = cfg2.telemetry.build()
    assert isinstance(tel.tracer.clock, obs.FakeClock)
    cfg.telemetry.clock = "sundial"
    with pytest.raises(ConfigError, match="clock"):
        cfg.validate()
