"""``DealConfig`` — one declarative, serializable config tree for the
whole Deal pipeline (construction -> sampling -> partition -> executor ->
store/engine/QoS), with exact JSON round-trip and eager validation.

Every entry point (launchers, examples, benchmarks) is a thin client
that builds one of these and hands it to ``api.session.Session``; a
full run is reproducible from the JSON artifact alone because every
random draw in the pipeline is seeded from the config.

Design rules:

  * ``from_dict(to_dict(cfg)) == cfg`` and ``from_json(to_json(cfg)) ==
    cfg`` are EXACT (dataclass equality) — dump a config, check it in,
    and the rerun is the same run.
  * ``validate()`` checks every field eagerly and reports ALL problems
    in one error, each prefixed with its dotted field path
    (``store.evict_policy: ...``) — never just the first one.
  * names that select plugins (executor, model, evict_policy,
    admission) validate against the live registries
    (``api.registry``), so a third-party registration is immediately a
    legal config value.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional, Tuple

from repro.api import registry as _reg


class ConfigError(ValueError):
    """Raised by ``DealConfig.validate`` with every bad field listed."""


def _load_builtin_plugins() -> None:
    """Importing the defining modules registers the built-in plugins
    (executors in ``core.ops``, models in ``core.gnn_models``, eviction/
    admission in ``gnnserve.store``).  Local imports: config stays
    importable without pulling jax until validation time."""
    import repro.core.gnn_models   # noqa: F401
    import repro.core.ops          # noqa: F401
    import repro.gnnserve.store    # noqa: F401


# ----------------------------------------------------------------------
# the spec tree
# ----------------------------------------------------------------------

@dataclasses.dataclass
class GraphSpec:
    """Stage 1+2: dataset -> distributed CSR -> layer-wise sampling."""
    dataset: str = "ogbn-products"  # named dataset, or "rmat" (explicit)
    scale: float = 1.0              # node-count multiplier (CI smoke)
    n_nodes: int = 0                # dataset == "rmat" only
    avg_degree: int = 0             # dataset == "rmat": E = n * avg_degree
    fanout: int = 8                 # fixed fanout of the layer graphs
    seed: int = 0                   # dataset + sampling + features seed
    n_construct_workers: int = 4    # distributed CSR construction width


@dataclasses.dataclass
class ModelSpec:
    """Which registered GNN model, its depth and widths."""
    name: str = "gcn"
    n_layers: int = 3
    d_feature: int = 64
    heads: int = 1                  # attention heads (gat)


@dataclasses.dataclass
class PartitionSpec:
    """The 1-D collaborative partition geometry: ``p`` graph partitions
    x ``m`` feature partitions (the ("data", "model") mesh)."""
    p: int = 2
    m: int = 1


@dataclasses.dataclass
class ExecutorSpec:
    """Backend selection + the construction/validation logic that used
    to be copy-pasted across every launcher.

    ``fused_gather`` / ``block_table`` are first-class pallas kernel
    knobs (the fused gather+spmm path and the autotuned block-size
    table source — "default" = ``configs/tuned_blocks.json``); left at
    None they are omitted entirely, so executors that don't take them
    never see them."""
    name: str = "ref"               # a registered executor
    fallback_to_ref: bool = True    # dist on a trivial (p*m <= 1) mesh
    options: Dict[str, Any] = dataclasses.field(default_factory=dict)
    fused_gather: Optional[bool] = None
    block_table: Optional[str] = None

    def _options(self) -> Dict[str, Any]:
        opts = dict(self.options)
        if self.fused_gather is not None:
            opts.setdefault("fused_gather", self.fused_gather)
        if self.block_table is not None:
            opts.setdefault("block_table", self.block_table)
        return opts

    def build(self, partition: Optional[PartitionSpec] = None, *,
              n_nodes: Optional[int] = None):
        """Resolve this spec into an executor INSTANCE — the one place
        that owns the device-count check, the dist -> ref fallback on a
        trivial mesh, the dist geometry checks, and mesh creation.
        Raises ``ConfigError`` naming the offending field; unknown
        executor names list every registered one."""
        _load_builtin_plugins()
        if self.name not in _reg.EXECUTORS:
            raise ConfigError(
                f"executor.name: unknown executor {self.name!r}; "
                f"registered: {', '.join(_reg.EXECUTORS.names())}")
        from repro.core.ops import get_executor
        if self.name != "dist":
            return get_executor(self.name, **self._options())

        part = partition or PartitionSpec()
        p, m = part.p, part.m
        if p * m <= 1 and self.fallback_to_ref:
            return get_executor("ref")      # no mesh to run on
        import jax
        if len(jax.devices()) < p * m:
            raise ConfigError(
                f"executor.name: \"dist\" needs p*m = {p * m} devices "
                f"(found {len(jax.devices())} {jax.default_backend()}); "
                f"on a chip host, run on one with {p * m} chips; on the "
                f"CPU, run under JAX_PLATFORMS=cpu XLA_FLAGS="
                f"--xla_force_host_platform_device_count={p * m}")
        if n_nodes is not None and n_nodes % p != 0:
            raise ConfigError(
                f"partition.p: {p} must divide the node count {n_nodes}")
        if m & (m - 1) != 0:
            raise ConfigError(
                f"partition.m: {m} must be a power of two "
                "(row-subset pad buckets)")
        from repro.launch.mesh import make_host_mesh
        return get_executor("dist", mesh=make_host_mesh(p, m),
                            **self.options)


@dataclasses.dataclass
class StoreSpec:
    """The versioned embedding store: sharding, memory budget, and
    incremental node onboarding."""
    n_shards: int = 4
    budget_rows: int = 0            # 0 = unbudgeted; else rows per level
    evict_policy: str = "heat"      # a registered eviction policy
    admission: str = "probation"    # a registered admission policy
    onboarding: str = "none"        # "tail": node adds append a tail
    #                                 partition served via delta refresh


@dataclasses.dataclass
class QoSSpec:
    """Serving and freshness: the engine's batching geometry plus the
    optional multi-tenant schedule (empty ``tenants`` = single implicit
    tenant at ``staleness_bound``)."""
    staleness_bound: int = 64
    batch_slots: int = 4
    rows_per_step: int = 256
    refresh_charge: float = 1.0
    tenants: Tuple[Dict[str, Any], ...] = ()

    def tenant_registry(self):
        """The runtime ``gnnserve.qos.TenantRegistry`` (None when no
        tenants are declared)."""
        if not self.tenants:
            return None
        from repro.gnnserve.qos import TenantRegistry, TenantSpec
        return TenantRegistry([TenantSpec(**dict(t)) for t in self.tenants])


@dataclasses.dataclass
class RefreshSpec:
    """Delta re-inference knobs: the content-addressed resample seed
    and the dist frontier-size cutover — a refresh layer whose gathered
    universe is below ``dist_local_cutover`` rows runs on a local
    executor instead of the mesh (0 = never cut over; routing decisions
    surface in ``Session.stats()`` and the ``refresh.route`` trace
    spans).

    ``chunk_rows`` makes refresh preemptible under QoS: the delta
    frontier splits into chunks of this many rows and the scheduler
    interleaves them with tenant gathers, one chunk per serve step
    (0 = the whole refresh runs inline inside one step).  Chunking is
    bitwise-invariant — any value serves the exact bits of the inline
    refresh."""
    sample_seed: int = 0
    dist_local_cutover: int = 0
    chunk_rows: int = 0


@dataclasses.dataclass
class TelemetrySpec:
    """The ``repro.obs`` layer: per-Session span tracing + unified
    metrics.  Disabled by default — the no-op mode's overhead at every
    instrumentation site is a single attribute check, so leaving the
    hooks compiled in costs nothing measurable.

    The serving-tier health extras (all gated on ``enabled``):
    ``http_port`` starts a stdlib Prometheus/JSON scrape endpoint on
    ``Session.serve()`` (-1 = off, 0 = an ephemeral port published as
    ``session.endpoint.port``); ``snapshot_path`` adds a periodic JSON
    stats-snapshot writer; the ``health_*`` / ``slo_*`` fields tune the
    engine's burn-rate monitor (rolling window length, SLO error
    budget, the burn rate at which an alert fires, and an optional
    wall-clock queue-wait SLO in ms applied to every tenant — 0
    disables the wait detector)."""
    enabled: bool = False
    capacity: int = 65536           # span ring-buffer size (oldest drop)
    clock: str = "monotonic"        # "monotonic" | "fake" (deterministic
    #                                 auto-advancing test clock)
    http_port: int = -1             # -1 = no endpoint, 0 = ephemeral
    snapshot_path: str = ""         # "" = no periodic JSON snapshots
    snapshot_every_s: float = 1.0
    health_window: int = 128        # rolling-window observations
    slo_error_budget: float = 0.01  # allowed violating fraction
    burn_threshold: float = 4.0     # alert at burn >= threshold
    wait_slo_ms: float = 0.0        # 0 = wait-burn detector off

    def build(self):
        """The runtime ``obs.Telemetry`` (None when disabled — the
        session then leaves the process-current telemetry alone)."""
        if not self.enabled:
            return None
        from repro import obs
        clock = obs.FakeClock() if self.clock == "fake" else None
        return obs.Telemetry(enabled=True, clock=clock,
                             capacity=self.capacity)


@dataclasses.dataclass
class ClusterSpec:
    """Multi-process serving tier (``gnnserve.cluster``): shard-worker
    processes along the existing 1-D partitioning behind an RPC router.

    ``n_shards = 0`` (the default) keeps single-process serving;
    ``n_shards > 0`` makes ``Session.serve()`` spawn that many
    ``ShardWorker`` processes, health-check their readiness, and return
    a router-backed engine with the same surface — existing clients
    don't change.  ``ports`` pins worker ports (empty = ephemeral,
    published via per-shard port files in ``run_dir``); ``http_port``
    starts the router's aggregated ``/healthz`` + ``/stats`` endpoint.
    ``run_dir`` holds the per-shard WAL segments and world checkpoints
    that make kill/restart/replay bitwise ("" = a fresh temp dir, so
    restarts within one deployment replay but nothing persists across
    deployments).  ``overrides`` tunes individual shards — entries are
    dicts with a ``shard`` index plus any of ``budget_rows`` /
    ``evict_policy`` / ``admission`` (store) or ``staleness_bound`` /
    ``batch_slots`` / ``rows_per_step`` (engine geometry); none of
    these change served bytes (residency and batching are
    bitwise-invariant), only footprint and scheduling."""
    n_shards: int = 0               # 0 = single-process serving
    host: str = "127.0.0.1"
    ports: Tuple[int, ...] = ()     # empty = ephemeral ports
    http_port: int = -1             # router endpoint; -1 off, 0 ephemeral
    run_dir: str = ""               # "" = fresh temp dir per deployment
    ready_timeout_s: float = 120.0  # worker world build/restore budget
    hang_timeout_s: float = 60.0    # heartbeat staleness => wedged
    overrides: Tuple[Dict[str, Any], ...] = ()


_OVERRIDE_FIELDS = ("shard", "budget_rows", "evict_policy", "admission",
                    "staleness_bound", "batch_slots", "rows_per_step")

_TENANT_FIELDS = ("name", "priority", "slot_quota", "rate", "staleness_slo")


def tenants_from_string(text: str) -> Tuple[Dict[str, Any], ...]:
    """The CLI ``--tenants`` format ("name:priority:quota:rate:slo,...")
    as config-tree tenant dicts — delegates to the canonical parser
    (``gnnserve.qos.parse_tenants``, including its TenantSpec value
    checks) and re-raises every problem as ``ConfigError``."""
    from repro.gnnserve.qos import parse_tenants
    try:
        reg = parse_tenants(text)
    except (ValueError, AssertionError) as exc:
        raise ConfigError(f"qos.tenants: {exc}") from None
    return tuple({"name": t.name, "priority": t.priority,
                  "slot_quota": t.slot_quota, "rate": t.rate,
                  "staleness_slo": t.staleness_slo} for t in reg)


# ----------------------------------------------------------------------
# the root
# ----------------------------------------------------------------------

_SECTIONS = {"graph": GraphSpec, "model": ModelSpec,
             "partition": PartitionSpec, "executor": ExecutorSpec,
             "store": StoreSpec, "qos": QoSSpec, "refresh": RefreshSpec,
             "telemetry": TelemetrySpec, "cluster": ClusterSpec}


@dataclasses.dataclass
class DealConfig:
    graph: GraphSpec = dataclasses.field(default_factory=GraphSpec)
    model: ModelSpec = dataclasses.field(default_factory=ModelSpec)
    partition: PartitionSpec = dataclasses.field(
        default_factory=PartitionSpec)
    executor: ExecutorSpec = dataclasses.field(
        default_factory=ExecutorSpec)
    store: StoreSpec = dataclasses.field(default_factory=StoreSpec)
    qos: QoSSpec = dataclasses.field(default_factory=QoSSpec)
    refresh: RefreshSpec = dataclasses.field(default_factory=RefreshSpec)
    telemetry: TelemetrySpec = dataclasses.field(
        default_factory=TelemetrySpec)
    cluster: ClusterSpec = dataclasses.field(default_factory=ClusterSpec)

    # -- serialization --------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        # JSON has no tuples; normalize here so to_dict output and a
        # json.loads round-trip are the same object shapes
        d["qos"]["tenants"] = [dict(t) for t in d["qos"]["tenants"]]
        d["cluster"]["ports"] = list(d["cluster"]["ports"])
        d["cluster"]["overrides"] = [dict(o)
                                     for o in d["cluster"]["overrides"]]
        return d

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "DealConfig":
        """Strict: an unknown section or field is an error that names
        it — a typo must not silently fall back to a default."""
        if not isinstance(d, dict):
            raise ConfigError(f"config root must be a dict, got {type(d)}")
        errors: List[str] = []
        kw = {}
        for key, sub in d.items():
            if key not in _SECTIONS:
                errors.append(f"{key}: unknown config section; valid: "
                              + ", ".join(_SECTIONS))
                continue
            if not isinstance(sub, dict):
                errors.append(f"{key}: must be a dict of fields, got "
                              f"{type(sub).__name__}")
                continue
            spec_cls = _SECTIONS[key]
            known = {f.name for f in dataclasses.fields(spec_cls)}
            bad = [f"{key}.{k}: unknown field; valid: " + ", ".join(known)
                   for k in sub if k not in known]
            if bad:
                errors.extend(bad)
                continue
            kw[key] = spec_cls(**sub)
        if errors:
            raise ConfigError("invalid DealConfig:\n  - "
                              + "\n  - ".join(errors))
        cfg = cls(**kw)
        if isinstance(cfg.qos.tenants, (list, tuple)):
            # normalize JSON lists to tuples for exact dataclass
            # equality; non-dict entries pass through for validate()
            # to name
            cfg.qos.tenants = tuple(dict(t) if isinstance(t, dict) else t
                                    for t in cfg.qos.tenants)
        if isinstance(cfg.cluster.ports, (list, tuple)):
            cfg.cluster.ports = tuple(cfg.cluster.ports)
        if isinstance(cfg.cluster.overrides, (list, tuple)):
            cfg.cluster.overrides = tuple(
                dict(o) if isinstance(o, dict) else o
                for o in cfg.cluster.overrides)
        return cfg

    def to_json(self, indent: int = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent, sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "DealConfig":
        return cls.from_dict(json.loads(text))

    @classmethod
    def load(cls, path) -> "DealConfig":
        with open(path) as f:
            return cls.from_json(f.read())

    def dump(self, path) -> None:
        with open(path, "w") as f:
            f.write(self.to_json() + "\n")

    # -- validation -----------------------------------------------------
    def _type_errors(self) -> List[str]:
        """Per-field type check against each spec's defaults — runs (and
        raises) BEFORE the value checks, which assume sane types.  bool
        is not an int here; int is an acceptable float."""
        errs = []
        for sec in _SECTIONS:
            spec = getattr(self, sec)
            if not isinstance(spec, _SECTIONS[sec]):
                errs.append(f"{sec}: must be a {_SECTIONS[sec].__name__}")
                continue
            defaults = _SECTIONS[sec]()
            for f in dataclasses.fields(spec):
                v = getattr(spec, f.name)
                d = getattr(defaults, f.name)
                if isinstance(d, bool):
                    ok = isinstance(v, bool)
                elif isinstance(d, int):
                    ok = isinstance(v, int) and not isinstance(v, bool)
                elif isinstance(d, float):
                    ok = (isinstance(v, (int, float))
                          and not isinstance(v, bool))
                elif isinstance(d, str):
                    ok = isinstance(v, str)
                elif isinstance(d, dict):
                    ok = isinstance(v, dict)
                elif isinstance(d, tuple):
                    ok = isinstance(v, (list, tuple))
                else:
                    ok = True
                if not ok:
                    errs.append(f"{sec}.{f.name}: expected "
                                f"{type(d).__name__}, got "
                                f"{type(v).__name__} ({v!r})")
        return errs

    def validate(self) -> "DealConfig":
        """Eagerly check every field; raise one ``ConfigError`` listing
        EVERY bad field by dotted path.  Returns self (chainable)."""
        _load_builtin_plugins()
        from repro.core.graph import dataset_names
        type_errors = self._type_errors()
        if type_errors:
            raise ConfigError("invalid DealConfig:\n  - "
                              + "\n  - ".join(type_errors))
        e: List[str] = []
        g, m, pt, ex = self.graph, self.model, self.partition, self.executor
        st, q, r = self.store, self.qos, self.refresh

        known = dataset_names() + ["rmat"]
        if g.dataset not in known:
            e.append(f"graph.dataset: unknown dataset {g.dataset!r}; "
                     f"valid: {', '.join(known)}")
        if g.dataset == "rmat":
            if g.n_nodes <= 0:
                e.append("graph.n_nodes: must be > 0 for dataset \"rmat\"")
            if g.avg_degree <= 0:
                e.append("graph.avg_degree: must be > 0 for dataset "
                         "\"rmat\"")
        if g.scale <= 0:
            e.append(f"graph.scale: must be > 0, got {g.scale}")
        if g.fanout < 1:
            e.append(f"graph.fanout: must be >= 1, got {g.fanout}")
        if g.n_construct_workers < 1:
            e.append("graph.n_construct_workers: must be >= 1, got "
                     f"{g.n_construct_workers}")

        if m.name not in _reg.MODELS:
            e.append(f"model.name: unknown model {m.name!r}; registered: "
                     + ", ".join(_reg.MODELS.names()))
        if m.n_layers < 1:
            e.append(f"model.n_layers: must be >= 1, got {m.n_layers}")
        if m.d_feature < 1:
            e.append(f"model.d_feature: must be >= 1, got {m.d_feature}")
        if m.heads < 1:
            e.append(f"model.heads: must be >= 1, got {m.heads}")
        elif m.d_feature % m.heads != 0:
            e.append(f"model.heads: {m.heads} must divide d_feature "
                     f"{m.d_feature}")

        if pt.p < 1:
            e.append(f"partition.p: must be >= 1, got {pt.p}")
        if pt.m < 1:
            e.append(f"partition.m: must be >= 1, got {pt.m}")
        elif (ex.name == "dist" and m.heads >= 1
              and m.heads % pt.m and pt.m % m.heads):
            e.append(f"model.heads: {m.heads} heads cannot be split over "
                     f"partition.m: {pt.m} feature partitions (the dist "
                     "executor gives each model shard whole heads, or a "
                     "whole share of one): one must divide the other")

        if ex.name not in _reg.EXECUTORS:
            e.append(f"executor.name: unknown executor {ex.name!r}; "
                     f"registered: {', '.join(_reg.EXECUTORS.names())}")
        if not isinstance(ex.options, dict):
            e.append("executor.options: must be a dict, got "
                     f"{type(ex.options).__name__}")
        if ex.fused_gather is not None and not isinstance(
                ex.fused_gather, bool):
            e.append("executor.fused_gather: must be a bool or None, "
                     f"got {ex.fused_gather!r}")
        if ex.block_table is not None and not isinstance(
                ex.block_table, str):
            e.append("executor.block_table: must be a str or None, "
                     f"got {ex.block_table!r}")

        if st.n_shards < 1:
            e.append(f"store.n_shards: must be >= 1, got {st.n_shards}")
        if st.budget_rows < 0:
            e.append(f"store.budget_rows: must be >= 0 (0 = unbudgeted), "
                     f"got {st.budget_rows}")
        if st.evict_policy not in _reg.EVICT_POLICIES:
            e.append(f"store.evict_policy: unknown policy "
                     f"{st.evict_policy!r}; registered: "
                     + ", ".join(_reg.EVICT_POLICIES.names()))
        if st.admission not in _reg.ADMISSIONS:
            e.append(f"store.admission: unknown policy {st.admission!r}; "
                     f"registered: {', '.join(_reg.ADMISSIONS.names())}")
        if st.onboarding not in ("none", "tail"):
            e.append(f"store.onboarding: must be \"none\" or \"tail\", "
                     f"got {st.onboarding!r}")

        if q.staleness_bound < 1:
            e.append(f"qos.staleness_bound: must be >= 1, got "
                     f"{q.staleness_bound}")
        if q.batch_slots < 1:
            e.append(f"qos.batch_slots: must be >= 1, got {q.batch_slots}")
        if q.rows_per_step < 1:
            e.append(f"qos.rows_per_step: must be >= 1, got "
                     f"{q.rows_per_step}")
        seen = set()
        _num = (int, float)
        tenant_types = {"name": (str, "str"), "priority": (_num, "number"),
                        "slot_quota": (int, "int"), "rate": (_num, "number"),
                        "staleness_slo": (int, "int")}
        for i, t in enumerate(q.tenants):
            path = f"qos.tenants[{i}]"
            if not isinstance(t, dict):
                e.append(f"{path}: must be a dict with fields "
                         + ", ".join(_TENANT_FIELDS))
                continue
            bad_types = False
            for k, v in t.items():
                if k not in _TENANT_FIELDS:
                    e.append(f"{path}.{k}: unknown tenant field; valid: "
                             + ", ".join(_TENANT_FIELDS))
                elif (not isinstance(v, tenant_types[k][0])
                      or isinstance(v, bool)):
                    e.append(f"{path}.{k}: expected {tenant_types[k][1]},"
                             f" got {type(v).__name__} ({v!r})")
                    bad_types = True
            if bad_types:
                continue            # value checks assume sane types
            name = t.get("name", "")
            if not name:
                e.append(f"{path}.name: required and non-empty")
            elif name in seen:
                e.append(f"{path}.name: duplicate tenant {name!r}")
            seen.add(name)
            if t.get("priority", 1.0) <= 0:
                e.append(f"{path}.priority: must be > 0, got "
                         f"{t.get('priority')}")
            if t.get("slot_quota", 1) < 0:
                e.append(f"{path}.slot_quota: must be >= 0, got "
                         f"{t.get('slot_quota')}")
            if t.get("staleness_slo", 64) < 1:
                e.append(f"{path}.staleness_slo: must be >= 1, got "
                         f"{t.get('staleness_slo')}")
        # (refresh.sample_seed's type is covered by the type pass above)
        if r.dist_local_cutover < 0:
            e.append(f"refresh.dist_local_cutover: must be >= 0 "
                     f"(0 = never cut over), got {r.dist_local_cutover}")
        if r.chunk_rows < 0:
            e.append(f"refresh.chunk_rows: must be >= 0 "
                     f"(0 = inline refresh), got {r.chunk_rows}")
        tel = self.telemetry
        if tel.capacity < 1:
            e.append(f"telemetry.capacity: must be >= 1, got "
                     f"{tel.capacity}")
        if tel.clock not in ("monotonic", "fake"):
            e.append(f"telemetry.clock: must be \"monotonic\" or "
                     f"\"fake\", got {tel.clock!r}")
        if not -1 <= tel.http_port <= 65535:
            e.append(f"telemetry.http_port: must be -1 (off), 0 "
                     f"(ephemeral) or a valid port, got {tel.http_port}")
        if tel.snapshot_every_s <= 0:
            e.append(f"telemetry.snapshot_every_s: must be > 0, got "
                     f"{tel.snapshot_every_s}")
        if tel.health_window < 2:
            e.append(f"telemetry.health_window: must be >= 2, got "
                     f"{tel.health_window}")
        if not 0 < tel.slo_error_budget <= 1:
            e.append(f"telemetry.slo_error_budget: must be in (0, 1], "
                     f"got {tel.slo_error_budget}")
        if tel.burn_threshold <= 0:
            e.append(f"telemetry.burn_threshold: must be > 0, got "
                     f"{tel.burn_threshold}")
        if tel.wait_slo_ms < 0:
            e.append(f"telemetry.wait_slo_ms: must be >= 0 (0 = wait "
                     f"detector off), got {tel.wait_slo_ms}")

        cl = self.cluster
        if cl.n_shards < 0:
            e.append(f"cluster.n_shards: must be >= 0 (0 = single-"
                     f"process serving), got {cl.n_shards}")
        if cl.ports and len(cl.ports) != cl.n_shards:
            e.append(f"cluster.ports: need one port per shard "
                     f"({cl.n_shards}) or none (ephemeral), got "
                     f"{len(cl.ports)}")
        for i, p in enumerate(cl.ports):
            if not (isinstance(p, int) and not isinstance(p, bool)
                    and 1 <= p <= 65535):
                e.append(f"cluster.ports[{i}]: must be a valid port, "
                         f"got {p!r}")
        if not -1 <= cl.http_port <= 65535:
            e.append(f"cluster.http_port: must be -1 (off), 0 "
                     f"(ephemeral) or a valid port, got {cl.http_port}")
        if cl.ready_timeout_s <= 0:
            e.append(f"cluster.ready_timeout_s: must be > 0, got "
                     f"{cl.ready_timeout_s}")
        if cl.hang_timeout_s <= 0:
            e.append(f"cluster.hang_timeout_s: must be > 0, got "
                     f"{cl.hang_timeout_s}")
        for i, ov in enumerate(cl.overrides):
            path = f"cluster.overrides[{i}]"
            if not isinstance(ov, dict):
                e.append(f"{path}: must be a dict with fields "
                         + ", ".join(_OVERRIDE_FIELDS))
                continue
            for k in ov:
                if k not in _OVERRIDE_FIELDS:
                    e.append(f"{path}.{k}: unknown override field; "
                             f"valid: " + ", ".join(_OVERRIDE_FIELDS))
            shard = ov.get("shard")
            if not (isinstance(shard, int) and not isinstance(shard, bool)
                    and 0 <= shard < max(cl.n_shards, 1)):
                e.append(f"{path}.shard: must be a shard index in "
                         f"[0, {cl.n_shards}), got {shard!r}")
            ev = ov.get("evict_policy")
            if ev is not None and ev not in _reg.EVICT_POLICIES:
                e.append(f"{path}.evict_policy: unknown policy {ev!r}; "
                         f"registered: "
                         + ", ".join(_reg.EVICT_POLICIES.names()))
            adm = ov.get("admission")
            if adm is not None and adm not in _reg.ADMISSIONS:
                e.append(f"{path}.admission: unknown policy {adm!r}; "
                         f"registered: "
                         + ", ".join(_reg.ADMISSIONS.names()))
            for k in ("budget_rows",):
                if k in ov and (not isinstance(ov[k], int)
                                or isinstance(ov[k], bool)
                                or ov[k] < 0):
                    e.append(f"{path}.{k}: must be an int >= 0, got "
                             f"{ov[k]!r}")
            for k in ("staleness_bound", "batch_slots", "rows_per_step"):
                if k in ov and (not isinstance(ov[k], int)
                                or isinstance(ov[k], bool)
                                or ov[k] < 1):
                    e.append(f"{path}.{k}: must be an int >= 1, got "
                             f"{ov[k]!r}")
        if cl.n_shards > 0 and ex.name == "dist":
            e.append("cluster.n_shards: the dist executor inside "
                     "cluster workers needs per-process device flags; "
                     "run dist single-process or workers with "
                     "ref/pallas")

        if e:
            raise ConfigError("invalid DealConfig:\n  - "
                              + "\n  - ".join(e))
        return self
