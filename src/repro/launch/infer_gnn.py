"""DEAL end-to-end GNN inference launcher (the paper's pipeline, Fig 2).

A THIN CLIENT of the public API: argparse -> ``DealConfig`` ->
``api.Session`` (which owns construction, sampling, partitioning,
executor selection).  Every run is reproducible from one JSON artifact:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.infer_gnn \
      --dataset ogbn-products --model gcn --p 4 --m 2

  # dump the effective config, then reproduce the run from it alone
  python -m repro.launch.infer_gnn --model gat --dump-config run.json
  python -m repro.launch.infer_gnn --config run.json
"""
from __future__ import annotations

import argparse

from repro.api import (ConfigError, DealConfig, ExecutorSpec, GraphSpec,
                       ModelSpec, PartitionSpec, Session)
from repro.runtime import enable_compile_cache


def _run_session(cfg: DealConfig):
    try:
        s = Session.build(cfg)
    except ConfigError as e:
        raise SystemExit(str(e))
    cs = s.construct_stats
    print(f"[construct] {s.n_nodes} nodes, {s.graph.n_edges} edges in "
          f"{s.timings['construct_s']:.2f}s "
          f"(exchange {cs['exchanged_bytes']/1e6:.1f} MB)")
    print(f"[sample] {cfg.model.n_layers} layer graphs, "
          f"fanout {cfg.graph.fanout} in {s.timings['sample_s']:.2f}s")
    H = s.infer_all()
    t_inf = s.timings["infer_s"]
    print(f"[infer] embeddings {H.shape} for ALL nodes in {t_inf:.2f}s "
          f"({s.graph.n_edges/max(t_inf,1e-9)/1e6:.2f} M edges/s, "
          f"executor={s.executor.name})")
    return H


def run(dataset: str, model: str = "gcn", p: int = 2, m: int = 1,
        fanout: int = 8, n_layers: int = 3, d_feature: int = 64,
        seed: int = 0, distributed: bool = True, executor: str = "dist",
        scale: float = 1.0):
    """DEPRECATED shim — the pre-API entry point, kept for callers.
    Builds the equivalent ``DealConfig`` and delegates to ``Session``;
    outputs are bitwise-unchanged (tests/test_api.py proves it).
    ``executor`` selects the backend: "dist" (mesh, needs p*m devices;
    falls back to "ref" when the mesh is trivial), "ref" (single-host
    jnp oracle) or "pallas" (the Pallas kernels)."""
    if executor == "dist" and not distributed:
        executor = "ref"                # no mesh to run on — jnp oracle
    cfg = DealConfig(
        graph=GraphSpec(dataset=dataset, scale=scale, fanout=fanout,
                        seed=seed, n_construct_workers=p),
        model=ModelSpec(name=model, n_layers=n_layers,
                        d_feature=d_feature),
        partition=PartitionSpec(p=p, m=m),
        executor=ExecutorSpec(name=executor))
    return _run_session(cfg)


def config_from_args(args) -> DealConfig:
    executor = "ref" if (args.executor == "dist" and args.local) \
        else args.executor
    return DealConfig(
        graph=GraphSpec(dataset=args.dataset, scale=args.scale,
                        fanout=args.fanout, seed=args.seed,
                        n_construct_workers=args.p),
        model=ModelSpec(name=args.model, n_layers=args.layers,
                        d_feature=args.d_feature),
        partition=PartitionSpec(p=args.p, m=args.m),
        executor=ExecutorSpec(name=executor))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=None, metavar="CFG.json",
                    help="load the full DealConfig from a JSON artifact "
                         "(overrides every pipeline flag)")
    ap.add_argument("--dump-config", default=None, metavar="OUT.json",
                    help="write the effective DealConfig ('-' = stdout) "
                         "and exit without running")
    ap.add_argument("--dataset", default="ogbn-products")
    ap.add_argument("--model", default="gcn")
    ap.add_argument("--p", type=int, default=2, help="graph partitions")
    ap.add_argument("--m", type=int, default=1, help="feature partitions")
    ap.add_argument("--fanout", type=int, default=8)
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--d-feature", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="scale the dataset's node count (CI smoke)")
    ap.add_argument("--local", action="store_true")
    ap.add_argument("--executor", default="dist",
                    help="backend: dist mesh / ref jnp / pallas kernels "
                         "(or any registered executor)")
    args = ap.parse_args()
    try:
        cfg = (DealConfig.load(args.config) if args.config
               else config_from_args(args))
        cfg.validate()
    except ConfigError as e:
        raise SystemExit(str(e))
    if args.dump_config:
        if args.dump_config == "-":
            print(cfg.to_json())
        else:
            cfg.dump(args.dump_config)
            print(f"[config] wrote {args.dump_config}")
        return
    enable_compile_cache()
    _run_session(cfg)


if __name__ == "__main__":
    main()
