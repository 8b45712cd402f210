"""gnnserve — online embedding serving on top of DEAL's layerwise engine.

Architecture overview
=====================

The offline pipeline (graph -> layer-wise sampling -> partition ->
``run_model``) produces embeddings for ALL nodes.  gnnserve
turns that batch artifact into an online service that stays fresh as the
graph mutates, without re-running full epochs:

  ``store``      Versioned, partition-sharded embedding store holding
                 EVERY level of the layerwise computation (features,
                 each layer's input, final embedding).  Double-buffered:
                 writers stage copy-on-write shards, ``commit`` swaps
                 them in atomically (the epoch flip readers never see).
                 Memory-budgeted: ``budget_rows`` caps residency per
                 level; cold shards are evicted (heat/LRU) and misses
                 rebuild exactly the missing rows through the delta
                 engine (``RecomputeOnMiss``), bitwise-equal to a
                 never-evicted store (docs/ARCHITECTURE.md: "The store's
                 memory model").

  ``mutations``  Edge/node mutation log + CSR delta overlay over
                 ``core.graph.Graph``.  ``apply_edge_mutations`` splices
                 only the affected CSR rows — O(changed rows), not O(E).

  ``delta``      Incremental re-inference.  Edge churn deterministically
                 re-samples the affected layer-graph rows; the k-hop
                 forward-affected frontier is computed in closed form
                 from reversed fanout matrices (the forward twin of
                 ``core.sharing``'s backward dependency walk), and ONLY
                 those rows re-run through the pluggable executor layer
                 (``core.ops``: ref / pallas / dist with a per-partition
                 frontier split on the mesh) — bitwise-identical to a
                 from-scratch epoch through the same executor.

  ``engine``     Continuous-batching lookup engine (the fixed-slot
                 pattern of ``serve.engine``): B slots, one fused
                 sharded gather per step, and a staleness bound on
                 pending mutations that triggers delta refresh inline.

  ``qos``        Multi-tenant QoS scheduling: tenants declared with
                 priority / slot quota / token-bucket rate / per-tenant
                 staleness SLO replace the engine's single global bound
                 and FIFO queue.  Slots and the per-step row budget are
                 split deficit-weighted-fair (work-conserving, with
                 preemptive quota reclaim and a K-step starvation
                 bound); refresh planning is deadline-driven off the
                 tightest ACTIVE tenant SLO, with lagged per-tenant
                 epoch views — each tenant's reads are bitwise-equal to
                 a single-tenant engine run at that tenant's SLO
                 (content-addressed resampling makes refresh batching
                 invariant).

Dataflow:  queries ->  engine.step -> store.lookup (front buffer)
           mutations -> MutationLog -> [staleness bound trips]
                     -> apply_edge_mutations -> resample_rows
                     -> forward_frontier -> row-subset re-inference
                     -> store.commit (buffer swap, version += 1)

Node additions onboard INCREMENTALLY on stores built with
``onboarding="tail"``: a tail partition appends past the main 1-D
partitioning, the new ids ride the next refresh's resampled set, and
``engine.full_epoch()`` folds tails back in (bitwise-unchanged).

Entry points (all thin clients of ``repro.api`` — DealConfig +
Session): ``launch/serve_embeddings.py`` (CLI service loop),
``examples/embedding_service.py`` (demo), and
``benchmarks/bench_incremental.py`` (delta vs full-recompute study).
"""
from repro.gnnserve.delta import (DeltaReinference, RecomputeOnMiss,
                                  RefreshJob, attach_recompute,
                                  build_reverse_index, forward_frontier,
                                  resample_rows, splice_reverse_index)
from repro.gnnserve.engine import EmbeddingServeEngine, Query
from repro.gnnserve.mutations import (MutationBatch, MutationLog,
                                      apply_edge_mutations, grow_graph)
from repro.gnnserve.qos import (QoSScheduler, TenantRegistry, TenantSpec,
                                parse_tenants)
from repro.gnnserve.store import (EmbeddingStore, EvictedRowMiss,
                                  SnapshotMiss, StoreSnapshot,
                                  store_from_inference)

__all__ = ["DeltaReinference", "RecomputeOnMiss", "RefreshJob",
           "attach_recompute",
           "build_reverse_index", "forward_frontier",
           "resample_rows", "splice_reverse_index",
           "EmbeddingServeEngine", "Query",
           "MutationBatch", "MutationLog", "apply_edge_mutations",
           "grow_graph",
           "QoSScheduler", "TenantRegistry", "TenantSpec", "parse_tenants",
           "EmbeddingStore", "EvictedRowMiss", "SnapshotMiss",
           "StoreSnapshot", "store_from_inference"]
