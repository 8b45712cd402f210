"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only fig16-19,fig20]
                                          [--executor ref|pallas|dist]
                                          [--smoke]

Prints ``name,us_per_call,derived`` CSV rows.  Real runs MERGE their rows
into results/bench.csv by name (so partial/--only/--executor runs never
clobber other rows) and, per bench, write a ``results/BENCH_<key>.json``
stage-breakdown summary: the bench runs under its own telemetry, so
every instrumented span in the pipeline (``ops.*``, ``store.*``,
``refresh.*``, ...) aggregates into a per-stage table for regression
tracking alongside the headline CSV numbers.  ``--smoke`` runs every
registered bench at tiny shapes as a CI liveness check and writes no
CSV/summary files.

EVERY invocation (``--smoke`` included) additionally appends one entry
to ``results/TRAJECTORY.json`` — the tracked bench trajectory.  Gate it
with ``python -m repro.obs.report --trajectory results/TRAJECTORY.json``:
the latest entry's per-stage share of each bench's span profile is
compared against the median of previous same-(executor, smoke) entries.
"""
import argparse
import importlib
import inspect
import json
import pathlib
import subprocess
import sys
import time
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmarks import common  # noqa: E402

from repro import obs  # noqa: E402
from repro.runtime import enable_compile_cache  # noqa: E402

MODULES = {
    "fig14": "benchmarks.bench_e2e",
    "tab5": "benchmarks.bench_sharing",
    "tab6": "benchmarks.bench_accuracy",
    "fig15": "benchmarks.bench_scaling",
    "fig16-19": "benchmarks.bench_primitives_dist",
    "fig20": "benchmarks.bench_graph_construction",
    "fig21": "benchmarks.bench_feature_prep",
    "fig3": "benchmarks.bench_breakdown",
    "incremental": "benchmarks.bench_incremental",
    "qos": "benchmarks.bench_qos",
    "kernels": "benchmarks.bench_kernels",
    "cluster": "benchmarks.bench_cluster",
}
ALIASES = {"e2e": "fig14"}


def _merge_csv(path: pathlib.Path, rows) -> None:
    """Merge rows into the CSV by name: replace same-name rows in place,
    append new ones, keep everything else."""
    header = "name,us_per_call,derived"
    old = []
    if path.exists():
        old = [ln for ln in path.read_text().splitlines()[1:] if ln]
    new_by_name = {r.split(",", 1)[0]: r for r in rows}   # last write wins
    out, seen = [], set()
    for ln in old:
        name = ln.split(",", 1)[0]
        if name in seen:                     # heal pre-existing dupes
            continue
        seen.add(name)
        out.append(new_by_name.pop(name, ln))
    appended = set()
    for r in rows:
        name = r.split(",", 1)[0]
        if name in new_by_name and name not in appended:
            out.append(new_by_name[name])
            appended.add(name)
    path.write_text(header + "\n" + "\n".join(out) + "\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("keys", nargs="*",
                    help="bench keys (same as --only), e.g. `run.py e2e`")
    ap.add_argument("--only", default=None,
                    help="comma list of keys: " + ",".join(MODULES))
    ap.add_argument("--executor", default="ref",
                    choices=["ref", "pallas", "dist"],
                    help="backend for benches that support retargeting")
    ap.add_argument("--config", default=None, metavar="CFG.json",
                    help="DealConfig JSON artifact passed to benches "
                         "that accept cfg= (e.g. incremental): retarget "
                         "a bench's world from one reproducible file")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, all benches, no bench.csv write "
                         "(CI liveness check)")
    args = ap.parse_args()
    enable_compile_cache()
    cfg = None
    if args.config:
        from repro.api import DealConfig
        cfg = DealConfig.load(args.config).validate()
    wanted = list(args.keys) + (args.only.split(",") if args.only else [])
    keys = [ALIASES.get(k, k) for k in wanted] if wanted else list(MODULES)
    keys = list(dict.fromkeys(keys))         # dedupe, keep order
    unknown = [k for k in keys if k not in MODULES]
    if unknown:
        sys.exit(f"unknown bench key(s) {unknown}; valid: "
                 f"{', '.join(list(MODULES) + list(ALIASES))}")
    print("name,us_per_call,derived")
    failures = []
    summaries = {}
    for k in keys:
        mod = importlib.import_module(MODULES[k])
        print(f"# === {k} ({MODULES[k]}) ===", flush=True)
        n_rows_before = len(common.ROWS)
        tel = obs.Telemetry(enabled=True)
        try:
            sig = inspect.signature(mod.run).parameters
            kw = {}
            if "smoke" in sig:
                kw["smoke"] = args.smoke
            if "executor" in sig:
                kw["executor"] = args.executor
            if "cfg" in sig and cfg is not None:
                kw["cfg"] = cfg
            with obs.use(tel):
                mod.run(**kw)
        except Exception as e:
            failures.append((k, e))
            print(f"# FAILED {k}: {e}")
            traceback.print_exc()
            continue
        summaries[k] = {
            "bench": k,
            "module": MODULES[k],
            "executor": args.executor,
            "rows": common.ROWS[n_rows_before:],
            "stages": tel.tracer.aggregate(),
            "metrics": tel.metrics.to_dict(),
            "trace_coverage": tel.tracer.coverage(),
            "n_spans": len(tel.tracer.events),
            "n_dropped_spans": tel.tracer.n_dropped,
        }
    out = pathlib.Path(__file__).resolve().parents[1] / "results"
    out.mkdir(exist_ok=True)
    if not args.smoke:
        if common.ROWS:
            _merge_csv(out / "bench.csv", common.ROWS)
        for k, summary in summaries.items():
            p = out / f"BENCH_{k.replace('-', '_')}.json"
            p.write_text(json.dumps(summary, indent=1, sort_keys=True)
                         + "\n")
            print(f"# wrote {p.relative_to(out.parent)} "
                  f"({len(summary['stages'])} stages)", flush=True)
    from repro.obs import report
    try:
        git = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=pathlib.Path(__file__).resolve().parents[1],
        ).stdout.strip() or "unknown"
    except Exception:
        git = "unknown"
    entry = {
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "git": git,
        "smoke": bool(args.smoke),
        "executor": args.executor,
        "failures": [k for k, _ in failures],
        "benches": {k: {"stages": s["stages"],
                        "coverage": s["trace_coverage"],
                        "n_spans": s["n_spans"]}
                    for k, s in summaries.items()},
    }
    traj = out / "TRAJECTORY.json"
    entries = report.append_trajectory(traj, entry)
    print(f"# appended trajectory entry #{len(entries)} to "
          f"{traj.relative_to(out.parent)}", flush=True)
    if failures:
        sys.exit(f"{len(failures)} benchmark group(s) failed: "
                 f"{[k for k, _ in failures]}")


if __name__ == "__main__":
    main()
