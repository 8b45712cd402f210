"""``Session`` — the one lifecycle object over the whole Deal pipeline.

    cfg = DealConfig(...)                       # or DealConfig.load(path)
    with Session.build(cfg) as s:
        H = s.infer_all()                       # offline: all-node epoch
        eng = s.serve()                         # online: store + engine
        s.apply_mutations().add_edges(src, dst)
        s.refresh()
        print(s.stats())

``build`` owns every stage the launchers used to hand-wire: dataset ->
distributed CSR construction -> layer-wise sampling -> feature/param
init -> executor selection (``ExecutorSpec.build``: device checks,
dist->ref fallback, mesh creation) — and ``serve`` adds the online
half: full epoch -> versioned store (budget / eviction / onboarding)
-> recompute-on-miss wiring -> continuous-batching engine with optional
multi-tenant QoS.  Every stage draws randomness only from the config's
seeds, so two Sessions built from equal configs are bitwise-identical
worlds — which is what makes the deprecation shims in the launchers
exactly equivalent to the code they replaced.

``infer_all`` runs the canonical full-graph path (``run_model`` over
the bound executor — op-for-op the pre-API launcher computation);
``serve`` builds its store from ``DeltaReinference.full_levels`` (the
delta engine's level layout), exactly as the serving launcher always
did.
"""
from __future__ import annotations

import copy
import time
from typing import Any, Dict, Optional

import numpy as np

from repro import obs
from repro.api.config import ConfigError, DealConfig
from repro.api.registry import MODELS


class Session:
    """Build once from a validated ``DealConfig``; drive offline
    inference and/or online serving; tear down with ``close``."""

    def __init__(self, cfg: DealConfig):
        # construct via build() for eager validation; __init__ assumes a
        # valid config
        self.cfg = cfg
        self._closed = False
        self.timings: Dict[str, float] = {}
        # telemetry first: the pipeline stages below record through it.
        # When enabled it becomes the PROCESS-current telemetry for the
        # session's lifetime (close() restores the previous one); when
        # disabled the current telemetry is left alone, so tests can
        # still scope their own via obs.use().
        self.telemetry = cfg.telemetry.build()
        self._prev_telemetry = (obs.install(self.telemetry)
                                if self.telemetry is not None else None)
        # session-scoped subset-plan cache counters: stats() must report
        # THIS session's hits/misses, not every session in the process
        from repro.core.partition import install_plan_cache_counters
        self._plan_cache_counters = install_plan_cache_counters()
        self._build_pipeline()
        self._H: Optional[np.ndarray] = None
        self._dist_bound = None      # (layer graphs, DistIOs) of infer_all
        self._n_epochs = 0           # infer_all epochs run, for span attrs
        self._engine = None
        self._endpoint = None
        self._cluster = None

    @classmethod
    def build(cls, cfg: DealConfig) -> "Session":
        """Validate eagerly (every bad field named) and assemble the
        offline pipeline.  The online half (store/engine) is built
        lazily by the first ``serve()``."""
        cfg.validate()
        return cls(cfg)

    # -- pipeline assembly ----------------------------------------------
    def _build_pipeline(self) -> None:
        import jax

        from repro.core.graph import (csr_from_edges_distributed,
                                      make_dataset, rmat_edges)
        from repro.core.sampler import sample_layer_graphs
        cfg = self.cfg
        g, m = cfg.graph, cfg.model

        with obs.span("construct.dataset") as sp:
            t0 = time.perf_counter()
            if g.dataset == "rmat":
                n = int(g.n_nodes * g.scale)
                src, dst = rmat_edges(n, int(n * g.avg_degree),
                                      seed=g.seed)
            else:
                src, dst, n = make_dataset(g.dataset, seed=g.seed,
                                           scale=g.scale)
            self.src, self.dst, self.n_nodes = src, dst, n
            if sp:
                sp.set(dataset=g.dataset, n_nodes=n, n_edges=src.size)
        self.graph, self.construct_stats = csr_from_edges_distributed(
            src, dst, n, n_workers=g.n_construct_workers)
        self.timings["construct_s"] = time.perf_counter() - t0

        t1 = time.perf_counter()
        with obs.span("sample.layer_graphs") as sp:
            self.layer_graphs = sample_layer_graphs(
                self.graph, fanout=g.fanout, n_layers=m.n_layers,
                seed=g.seed)
            if sp:
                sp.set(n_layers=m.n_layers, fanout=g.fanout)
        self.timings["sample_s"] = time.perf_counter() - t1

        with obs.span("featprep.init") as sp:
            rng = np.random.default_rng(g.seed)
            self.X = rng.standard_normal((n, m.d_feature),
                                         dtype=np.float32)
            dims = [m.d_feature] * (m.n_layers + 1)
            plugin = MODELS.get(m.name)
            self.params = plugin.init(jax.random.PRNGKey(g.seed), dims,
                                      heads=m.heads)
            if sp:
                sp.set(d_feature=m.d_feature, bytes=int(self.X.nbytes))
        with obs.span("session.executor_build",
                      {"executor": cfg.executor.name}):
            self.executor = cfg.executor.build(cfg.partition, n_nodes=n)

    # -- offline: all-node inference ------------------------------------
    def infer_all(self) -> np.ndarray:
        """One full layer-by-layer epoch for ALL nodes through the bound
        executor.  Cached; bitwise-identical to the pre-API launcher
        path (same spec interpreter, same graph bindings)."""
        self._check_open()
        if self._H is not None:
            return self._H
        from repro.core.gnn_models import model_spec
        from repro.core.ops import DenseIO, DistExecutor, run_model
        spec = model_spec(self.cfg.model.name, self.params)
        lgs = self.layer_graphs[:len(spec.layers)]
        ex = self.executor
        t0 = time.perf_counter()
        self._n_epochs += 1
        # phases by name, so a profiler trace tells host staging,
        # dispatch and the copy back apart (see ``obs.trace``)
        with obs.span("session.infer_all") as sp:
            if sp:
                sp.set(model=self.cfg.model.name, epoch=self._n_epochs)
            with obs.span("infer.bind"):
                if isinstance(ex, DistExecutor):
                    ios = self._dist_ios(ex, spec, lgs)
                else:
                    ios = [DenseIO.from_layer_graph(lg) for lg in lgs]
            with obs.span("infer.forward"):
                H = run_model(ex, spec, ios, self.X)
            with obs.span("infer.fetch"):
                self._H = np.asarray(H)
            with obs.span("infer.check"):
                assert not np.isnan(self._H).any()
            if sp:
                sp.set(rows=int(self._H.shape[0]))
        self.timings["infer_s"] = time.perf_counter() - t0
        return self._H

    def _dist_ios(self, ex, spec, lgs):
        """The mesh bindings (CommPlan and its device arrays), built on
        the first epoch and kept while the layer graphs are the same
        objects (the delta engine resamples its own copies)."""
        bound = self._dist_bound
        if bound is None or len(bound[0]) != len(lgs) or any(
                a is not b for a, b in zip(bound[0], lgs)):
            need_sddmm = any(op.kind == "attn_scores"
                             for layer in spec.layers for op in layer.ops)
            bound = (list(lgs), ex.bind(lgs, need_sddmm=need_sddmm))
            self._dist_bound = bound
        return bound[1]

    # -- online: store + serving engine ---------------------------------
    def serve(self):
        """Stand up (once) and return the online serving engine: full
        epoch -> versioned store (budget / eviction / tail onboarding)
        -> ``EmbeddingServeEngine`` with the config's QoS schedule.

        With ``cluster.n_shards > 0`` the engine is a router-backed
        ``ClusterEngine`` instead: shard-worker processes are spawned
        (each builds the same world from this config), readiness is
        health-checked, and the returned facade routes transparently —
        same surface, same served bytes."""
        self._check_open()
        if self._engine is not None:
            return self._engine
        cfg = self.cfg
        if cfg.cluster.n_shards > 0:
            from repro.gnnserve.cluster import ClusterDeployment
            with obs.span("serve.cluster_launch") as sp:
                self._cluster = ClusterDeployment(cfg)
                if sp:
                    sp.set(n_shards=cfg.cluster.n_shards)
            # the workers paid the epoch; the deployment's ready wait
            # (spawn -> world build -> socket up) is the launch cost
            self.timings["epoch_s"] = self._cluster.ready_wait_s
            self._engine = self._cluster.engine
            return self._engine
        from repro.gnnserve import (DeltaReinference, attach_recompute,
                                    store_from_inference)
        st = cfg.store
        self.reinfer = DeltaReinference(
            [copy.deepcopy(lg) for lg in self.layer_graphs],
            cfg.model.name, self.params,
            sample_seed=cfg.refresh.sample_seed, executor=self.executor,
            local_cutover=cfg.refresh.dist_local_cutover)
        t0 = time.perf_counter()
        with obs.span("serve.epoch") as sp:
            levels = self.reinfer.full_levels(self.X)
            if sp:
                sp.set(n_levels=len(levels))
        self.timings["epoch_s"] = time.perf_counter() - t0
        store = store_from_inference(
            self.X, levels[1:], n_shards=st.n_shards,
            budget_rows=st.budget_rows or None,
            evict_policy=st.evict_policy, admission=st.admission,
            onboarding=st.onboarding)
        if st.budget_rows:
            attach_recompute(store, self.reinfer)
        return self._attach_engine(store)

    def _attach_engine(self, store):
        """Wire a ready store (+ ``self.reinfer``/``self.graph``) into
        the serving engine, health options, and the telemetry endpoint.
        ``serve()`` calls this after the full epoch; checkpoint restore
        (``gnnserve.checkpoint.restore_into_session``) calls it with a
        restored store INSTEAD of running an epoch."""
        from repro.gnnserve import EmbeddingServeEngine
        cfg = self.cfg
        q = cfg.qos
        self._engine = EmbeddingServeEngine(
            store, self.reinfer, self.graph,
            batch_slots=q.batch_slots, rows_per_step=q.rows_per_step,
            staleness_bound=q.staleness_bound,
            tenants=q.tenant_registry(), refresh_charge=q.refresh_charge,
            refresh_chunk_rows=cfg.refresh.chunk_rows)
        t = cfg.telemetry
        self._engine.health_opts = {
            "window": t.health_window,
            "error_budget": t.slo_error_budget,
            "burn_threshold": t.burn_threshold,
            "wait_slo_ms": t.wait_slo_ms,
        }
        if self.telemetry is not None and (t.http_port >= 0
                                           or t.snapshot_path):
            from repro.obs.endpoint import TelemetryEndpoint
            self._endpoint = TelemetryEndpoint(
                self, port=t.http_port, snapshot_path=t.snapshot_path,
                snapshot_every_s=t.snapshot_every_s).start()
        return self._engine

    @classmethod
    def from_checkpoint(cls, path, cfg: DealConfig) -> "Session":
        """Build a Session whose serving world comes from a
        ``gnnserve.checkpoint.save_world`` artifact instead of a fresh
        full epoch: the offline pipeline still builds from ``cfg`` (the
        checkpoint stores no params/features below level 0), then the
        checkpointed graph/layer-graphs/store swap in and the engine
        attaches without recomputing the epoch.  The restored engine
        serves bitwise the rows the dumped one served."""
        cfg.validate()
        if cfg.cluster.n_shards > 0:
            raise ConfigError(
                "cluster.n_shards: from_checkpoint restores a single-"
                "process engine; cluster workers restore their own "
                "checkpoints via the deployment's run_dir")
        session = cls(cfg)
        from repro.gnnserve.checkpoint import restore_into_session
        restore_into_session(session, path)
        return session

    @property
    def cluster(self):
        """The live ``ClusterDeployment`` (None in single-process
        mode)."""
        return self._cluster

    @property
    def engine(self):
        """The serving engine (built on first access)."""
        return self.serve()

    @property
    def endpoint(self):
        """The live telemetry endpoint, or None (configure it via
        ``telemetry.http_port`` / ``telemetry.snapshot_path``)."""
        return self._endpoint

    @property
    def store(self):
        """The engine's CURRENT embedding store (a ``full_epoch`` fold
        swaps in a rebuilt one, so never cache this reference)."""
        return self.serve().store

    def apply_mutations(self):
        """The engine's writable mutation log (``add_edges`` /
        ``remove_edges`` / ``update_features`` / ``add_nodes``)."""
        return self.serve().mutate()

    def refresh(self) -> Dict[str, Any]:
        """Drain pending mutations into the store via delta
        re-inference (incremental node onboarding included when
        ``store.onboarding == "tail"``)."""
        return self.serve().refresh()

    def full_epoch(self, n_shards: Optional[int] = None) -> Dict[str, Any]:
        """Re-partition epoch: fold any onboarded tail partitions back
        into the main 1-D partitioning."""
        return self.serve().full_epoch(n_shards)

    # -- observability / lifecycle --------------------------------------
    def stats(self) -> Dict[str, Any]:
        """Pipeline timings + construction stats, plus the full serve/
        store/QoS counter tree once the engine exists (the legacy keys,
        unchanged), plus:

          ``plan_cache``   ``build_subset_plan_cached`` hit/miss counters
          ``metrics``      the flat UNIFIED metric view (``obs.compat``
                           naming: ``store.evictions``,
                           ``delta.frontier_rows.layer<l>``,
                           ``qos.tenant.<name>.*``, ...), with live
                           telemetry histograms merged on top when the
                           session runs with ``telemetry.enabled``.
          ``attribution``  per-tenant critical-path latency breakdowns
                           (queue_wait / pin / recompute / gather /
                           refresh_wait / sched_wait) once the engine
                           has served queries under telemetry.
          ``health``       SLO burn rates + structured alert events
                           from the serving-tier ``HealthMonitor``.
        """
        self._check_open()
        from repro.obs import compat
        out: Dict[str, Any] = {"n_nodes": self.n_nodes,
                               "n_edges": self.graph.n_edges,
                               **{f"t_{k}": v
                                  for k, v in self.timings.items()}}
        engine_stats = refresh_stats = cutover = None
        if self._cluster is not None:
            # router-merged tree: same engine/attribution/health schema
            # as the single-process branch below, plus a ``cluster``
            # subtree (per-shard statuses, restart count, router stats)
            merged = self._cluster.stats()
            out.update(merged)
            engine_stats = {
                k: v for k, v in merged.items()
                if k not in ("attribution", "health", "cluster",
                             "refresh_cutover")}
            refresh_stats = self._engine.last_refresh_stats
            cutover = merged.get("refresh_cutover")
        elif self._engine is not None:
            engine_stats = self._engine.stats()
            refresh_stats = self._engine.last_refresh_stats
            out.update(engine_stats)
            cutover = {
                "threshold": self.reinfer.local_cutover,
                "n_local": self.reinfer.n_local_cutovers,
                "n_dist": self.reinfer.n_dist_layers,
                "n_tail": self.reinfer.n_tail_routed}
            out["refresh_cutover"] = cutover
        out["plan_cache"] = dict(self._plan_cache_counters)
        out["metrics"] = compat.unified_metrics(
            engine_stats=engine_stats,
            construct_stats=self.construct_stats,
            refresh_stats=refresh_stats,
            plan_cache=out["plan_cache"],
            timings=self.timings,
            live=(self.telemetry.metrics.to_dict()
                  if self.telemetry is not None else None),
            cutover=cutover)
        if self._cluster is None:
            if (self._engine is not None
                    and self._engine.attrib is not None):
                out["attribution"] = self._engine.attrib.summary()
            if (self._engine is not None
                    and self._engine.health is not None):
                out["health"] = self._engine.health.summary()
        return out

    def dump_trace(self, path) -> Dict[str, Any]:
        """Write the session's span trace as Chrome/Perfetto trace-event
        JSON (load it at https://ui.perfetto.dev), with the metrics
        registry embedded under ``deal_metrics``.  Returns the document.
        Needs ``telemetry.enabled: true`` in the config."""
        self._check_open()
        if self.telemetry is None:
            raise ConfigError(
                "dump_trace needs telemetry enabled: set "
                "telemetry.enabled = true in the DealConfig")
        extra: Dict[str, Any] = {}
        attrib = getattr(self._engine, "attrib", None)
        health = getattr(self._engine, "health", None)
        if attrib is not None:
            extra["deal_attribution"] = attrib.summary()
            extra["deal_top_queries"] = attrib.top_paths()
        if health is not None:
            extra["deal_health"] = health.summary()
        return obs.dump_chrome_trace(
            self.telemetry.tracer, path, self.telemetry.metrics,
            process_name=f"deal.{self.cfg.model.name}",
            extra=extra or None)

    def prometheus_text(self) -> str:
        """The metrics registry in Prometheus exposition format (empty
        when telemetry is disabled)."""
        self._check_open()
        if self.telemetry is None:
            return ""
        return obs.prometheus_text(self.telemetry.metrics)

    def _check_open(self) -> None:
        if self._closed:
            raise ConfigError("session is closed")

    def close(self) -> None:
        """Release the big arrays (graph, features, store, engine) and
        hand the process-current telemetry back to whoever held it."""
        if not self._closed:
            if self._endpoint is not None:
                self._endpoint.stop()
                self._endpoint = None
            if self._cluster is not None:
                self._cluster.shutdown()
                self._cluster = None
            if self.telemetry is not None:
                obs.install(self._prev_telemetry)
            from repro.core.partition import uninstall_plan_cache_counters
            uninstall_plan_cache_counters(self._plan_cache_counters)
        self._closed = True
        self._engine = None
        for name in ("X", "graph", "layer_graphs", "reinfer", "_H",
                     "_dist_bound", "src", "dst", "params", "executor"):
            if hasattr(self, name):
                setattr(self, name, None)

    def __enter__(self) -> "Session":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
