"""End-to-end driver — the paper's workload (Fig 2): compute embeddings for
ALL nodes of a graph, distributed over a (P x M) device mesh.

Runs the full pipeline: on-disk edge list -> DEAL distributed CSR
construction -> layer-wise 1-hop sampling -> 1-D + feature collaborative
partition -> distributed layer-by-layer inference with the §3.4 primitives.

  PYTHONPATH=src python examples/allnode_inference.py            # 4x2 mesh
  PYTHONPATH=src python examples/allnode_inference.py --local    # 1 device
"""
import argparse
import os
import pathlib
import subprocess
import sys

sys.path.insert(0, "src")

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="ogbn-products")
    ap.add_argument("--model", default="gcn", choices=["gcn", "gat"])
    ap.add_argument("--p", type=int, default=4)
    ap.add_argument("--m", type=int, default=2)
    ap.add_argument("--local", action="store_true")
    args = ap.parse_args()

    if args.local:
        from repro.launch.infer_gnn import run
        run(args.dataset, args.model, p=1, m=1, distributed=False)
        return
    # the mesh needs P*M host devices — respawn with the forced count
    from repro.runtime import require_host_platform
    require_host_platform("examples/allnode_inference.py")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{args.p * args.m}")
    env["PYTHONPATH"] = str(ROOT / "src")
    code = (f"from repro.launch.infer_gnn import run; "
            f"run({args.dataset!r}, {args.model!r}, p={args.p}, "
            f"m={args.m}, distributed=True)")
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   cwd=str(ROOT))


if __name__ == "__main__":
    main()
