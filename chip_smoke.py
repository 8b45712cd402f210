#!/usr/bin/env python3
"""Bring-up smoke: the system's main path on a TPU, through ``DealConfig``
-> ``Session``, checked against the plain f32 reference.

    python chip_smoke.py              # one chip: epoch + serve phases
    python chip_smoke.py --chips 4    # four chips: dist GCN vs one chip

One chip (default), two phases in this one process:

  epoch  ``Session.infer_all`` for every node of the ogbn-papers100M
         stand-in at scale 32 (524,288 nodes, ~7.3M edges, fanout 8):
         3-layer GAT with 4 heads at d_feature=128 (the paper's model at
         papers100M's published feature width; weights random from the
         config seed).  Run with ``executor: "pallas"`` (the Pallas
         kernels, compiled), then with ``executor: "ref"`` (the plain f32
         jnp reference) on the same chip; the max abs difference relative
         to max|H_ref| must stay within TOL, and so must the shared
         f32 GEMM against a float64 product on the host.
  serve  ``Session.serve()`` on the pallas world: a few lookups through
         ``engine.submit``/``step`` (bitwise equal to the epoch's rows),
         one mutation batch, ``refresh()``, and every row of every level
         checked bitwise against a fresh full epoch through the same
         executor (the repo's delta-vs-full contract).

``--chips 4`` runs only the distributed epoch and what it is compared
with: GCN through ``DistExecutor`` on a p=2 x m=2 mesh of the four chips
(both axes of the paper's 1-D collaborative partitioning: the data-axis
ring and the model-axis psum) against ``RefExecutor`` on one chip, at the
same graph and width.  GCN, because the dist GAT scores with one head.

Sizes, per-phase seconds (compile = JAX backend compiles, persistent-
cache retrievals included) and errors go to stdout; the last line is one
JSON object.  The script exits non-zero, printing no result, when JAX
finds no TPU, when a Session got another executor than it asked for, or
when any check fails.  Times are bring-up observations, not benchmarks.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
# pallas (and dist) vs ref: f32 throughout (matmuls at HIGHEST precision),
# so the executors differ only in summation order and in the exp/divide
# of the softmax, a few f32 ulps (~1e-7) per op.  Three layers of 128-wide
# dots keep that near 1e-6 of max|H_ref|; 1e-5 leaves a decade of margin.
# On a TPU v5e the sound runs read 1.6e-7, and the other side, the same
# ref epoch with its matmuls at DEFAULT precision (bf16 passes), reads
# 4.1e-3 (GEMM guard: 1.7e-7 at HIGHEST, 2.4e-3 at DEFAULT; PERF.md).
TOL = 1e-5


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spent in backend compiles (persistent-cache retrievals
    included, since they replace a compile) and persistent-cache hits."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.hits = 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.secs += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def read(self):
        return self.secs, self.hits


class Phase:
    """Wall time, compile time and cache hits of one timed step."""

    def __init__(self, clock: CompileClock, name: str):
        self.clock, self.name = clock, name

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0, self.h0 = self.clock.read()
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            c1, h1 = self.clock.read()
            self.wall_s = time.perf_counter() - self.t0
            self.compile_s = c1 - self.c0
            log(f"[{self.name}] wall_s={self.wall_s:.3f} "
                f"compile_s={self.compile_s:.3f} "
                f"cache_hits={h1 - self.h0}")


def smoke_config(executor: str, *, model: str = "gat", scale: float = 32,
                 p: int = 1, m: int = 1):
    from repro.api import (DealConfig, ExecutorSpec, GraphSpec, ModelSpec,
                           PartitionSpec)
    return DealConfig(
        graph=GraphSpec(dataset="ogbn-papers100M", scale=scale, fanout=8,
                        seed=SEED),
        model=ModelSpec(name=model, n_layers=3, d_feature=128,
                        heads=4 if model == "gat" else 1),
        partition=PartitionSpec(p=p, m=m),
        executor=ExecutorSpec(name=executor, fallback_to_ref=False))


def build(cfg, clock):
    """``Session.build``, refusing a Session whose executor is not the
    one the config names."""
    from repro.api import Session
    with Phase(clock, f"build.{cfg.executor.name}"):
        s = Session.build(cfg)
    got = getattr(s.executor, "name", type(s.executor).__name__)
    if got != cfg.executor.name:
        raise SystemExit(f"asked for executor {cfg.executor.name!r}, "
                         f"got {got!r}")
    return s


def compare(name: str, H, H_ref) -> float:
    import numpy as np
    if H.shape != H_ref.shape or not np.isfinite(H).all():
        raise SystemExit(f"{name}: output shape {H.shape} vs "
                         f"{H_ref.shape}, or non-finite values")
    scale = float(np.abs(H_ref).max())
    err = float(np.abs(H - H_ref).max()) / scale
    log(f"[{name}] max|H - H_ref| / max|H_ref| = {err:.3e} "
        f"(max|H_ref| = {scale:.4f}, tol {TOL:g})")
    if not err <= TOL:
        raise SystemExit(f"{name}: error {err:.3e} above tolerance {TOL:g}")
    return err


def gemm_precision(ex) -> float:
    """The executors share one f32 GEMM, so pallas vs ref cannot see its
    precision: hold it to a float64 product made on the host."""
    import numpy as np
    rng = np.random.default_rng(SEED + 2)
    x = rng.standard_normal((4096, 128), dtype=np.float32)
    w = rng.standard_normal((128, 128), dtype=np.float32)
    return compare("gemm_vs_f64", np.asarray(ex.gemm(x, w)),
                   x.astype(np.float64) @ w)


def epoch_phase(clock, *, scale: float = 32):
    """All-node GAT epoch: pallas vs ref on the same chip.  Returns the
    pallas Session (kept for the serve phase) and its output."""
    s = build(smoke_config("pallas", scale=scale), clock)
    g, mdl = s.cfg.graph, s.cfg.model
    log(f"[epoch] {g.dataset} x{g.scale:g}: n_nodes={s.n_nodes} "
        f"n_edges={s.graph.n_edges} fanout={g.fanout} model={mdl.name} "
        f"layers={mdl.n_layers} heads={mdl.heads} d={mdl.d_feature}")
    with Phase(clock, "epoch.pallas"):
        H = s.infer_all()
    with build(smoke_config("ref", scale=scale), clock) as s_ref:
        with Phase(clock, "epoch.ref"):
            H_ref = s_ref.infer_all()
        gemm_precision(s_ref.executor)
    compare("epoch.pallas_vs_ref", H, H_ref)
    return s, H


def serve_phase(s, H, clock, *, n_queries: int = 4, rows: int = 256,
                n_edges: int = 64, n_feats: int = 16):
    """Serve the pallas world: lookups, one mutation batch, refresh, and
    the refreshed store against a fresh full epoch, bitwise."""
    import numpy as np

    from repro.gnnserve import DeltaReinference
    from repro.gnnserve.engine import Query
    rng = np.random.default_rng(SEED + 1)
    n = s.n_nodes
    with Phase(clock, "serve.epoch"):
        eng = s.serve()

    def answer(queries):
        for q in queries:
            eng.submit(q)
        steps = 0
        while not all(q.done for q in queries):
            if not eng.step() or steps > 10_000:
                raise SystemExit("serve: engine stopped with queries open")
            steps += 1
        return steps

    qs = [Query(uid=i, node_ids=rng.choice(n, rows, replace=False))
          for i in range(n_queries)]
    with Phase(clock, "serve.lookups"):
        steps = answer(qs)
    for q in qs:
        if not np.array_equal(q.out, H[q.node_ids]):
            raise SystemExit(f"serve: query {q.uid} rows differ from the "
                             f"epoch's")
    log(f"[serve] {n_queries} lookups x {rows} rows in {steps} steps, "
        f"bitwise equal to the epoch")

    d = s.cfg.model.d_feature
    muts = s.apply_mutations()
    muts.add_edges(rng.integers(0, n, n_edges), rng.integers(0, n, n_edges))
    ids = rng.choice(n, n_feats, replace=False).astype(np.int64)
    muts.update_features(ids, rng.standard_normal((n_feats, d),
                                                  dtype=np.float32))
    with Phase(clock, "serve.refresh"):
        st = s.refresh()
    log(f"[serve] refresh: {n_edges} edge adds + {n_feats} feature "
        f"updates, version={st['version']} "
        f"frontier_sizes={st['frontier_sizes']}")

    all_ids = np.arange(n, dtype=np.int64)
    ri = eng.reinfer
    with Phase(clock, "serve.full_epoch_oracle"):
        oracle = DeltaReinference(
            ri.layer_graphs, s.cfg.model.name, s.params,
            executor=s.executor).full_levels(s.store.lookup(all_ids, 0))
    for lvl in range(1, len(oracle)):
        got = s.store.lookup(all_ids, lvl)
        diff = float(np.abs(got - oracle[lvl]).max())
        log(f"[serve] level {lvl}: refreshed store vs full epoch max "
            f"diff {diff:.3e}")
        if not np.array_equal(got, oracle[lvl]):
            raise SystemExit(f"serve: refreshed level {lvl} is not bitwise "
                             f"equal to a full epoch (max diff {diff:.3e})")
    front = rng.choice(n, rows, replace=False)
    q = Query(uid=n_queries, node_ids=front)
    answer([q])
    if q.served_version != st["version"] or not np.array_equal(
            q.out, oracle[-1][front]):
        raise SystemExit("serve: post-refresh lookup is stale or wrong")
    log(f"[serve] post-refresh lookup served version {q.served_version}, "
        f"bitwise equal to the full epoch")


def dist_phase(clock, *, scale: float = 32):
    """GCN through DistExecutor on a p=2 x m=2 mesh vs RefExecutor on
    one chip."""
    s = build(smoke_config("dist", model="gcn", scale=scale, p=2, m=2),
              clock)
    with s:
        ids = [[d.id for d in row] for row in s.executor.mesh.devices]
        flat = [i for row in ids for i in row]
        log(f"[dist] mesh (data x model) device ids {ids}; "
            f"n_nodes={s.n_nodes} n_edges={s.graph.n_edges}")
        if len(set(flat)) != 4:
            raise SystemExit(f"dist: mesh needs 4 distinct chips, got {ids}")
        with Phase(clock, "epoch.dist"):
            H = s.infer_all()
    with build(smoke_config("ref", model="gcn", scale=scale), clock) as r:
        with Phase(clock, "epoch.ref_one_chip"):
            H_ref = r.infer_all()
    compare("epoch.dist_vs_ref", H, H_ref)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the distributed epoch on a 2x2 mesh")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX's devices are "
                         f"{len(devices)} x {dev.platform}); this smoke "
                         f"runs on a TPU chip only")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX found "
                         f"{len(devices)} TPU devices")
    from repro.kernels import ops as kops
    from repro.runtime import enable_compile_cache
    cache = enable_compile_cache()
    assert kops.on_tpu()
    log(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache}")

    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        dist_phase(clock)
    else:
        s, H = epoch_phase(clock)
        with s:
            serve_phase(s, H, clock)
    c, hits = clock.read()
    log(f"[total] wall_s={time.perf_counter() - t0:.3f} compile_s={c:.3f} "
        f"cache_hits={hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
