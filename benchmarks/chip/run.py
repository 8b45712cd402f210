#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/run.py --workload gat4-papers100m.epoch \
        --seed 7 --seconds 30 --trace 0

Set-up builds the cell's configuration through ``DealConfig`` ->
``Session.build`` (with the seed in place of the config's), puts in the
features and weights drawn from the seed, and warms up exactly the
shapes the cell's traffic uses.  The window then drives the traffic for
``--seconds``; with ``--trace 1`` a profiler trace covers the window
and the cell's per-layer metrics are read from it instead of the
end-to-end ones.  Afterwards the program's output is compared with the
plain reference (``reference.py``) and each number compared is printed
beside its limit.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``.  The run refuses, printing no result, where JAX
finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import bench          # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    with open("/proc/self/stat") as f:
        start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return up - start_ticks / os.sysconf("SC_CLK_TCK")


class CompileClock:
    """Backend compiles JAX ran (persistent-cache retrievals included,
    since they stand in for a compile): their count and seconds."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.count = 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.secs += secs
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)


class Spans:
    """The harness's own host spans, around its calls into each layer:
    kept in memory (name, start, end on ``perf_counter``) and, while a
    profiler trace runs, written into it as ``TraceAnnotation``s so the
    trace reduction can label idle device time by what the host did."""

    def __init__(self):
        self.done: List[Tuple[str, float, float]] = []
        self.tracing = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        ann = (jax.profiler.TraceAnnotation(name) if self.tracing
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            yield
        self.done.append((name, t0, time.perf_counter()))


class Run:
    """What a metric reader reads."""

    def __init__(self, cell: bench.Cell, device: Dict):
        self.device = device
        self.model = bench.load_model(cell)      # the model file
        self.model_cfg: Dict = {}                # DealConfig.model
        self.unit: str = ""
        self.units = 0
        self.unit_s: Optional[float] = None
        self.setup_s: Optional[float] = None
        self.compile_setup_s = 0.0
        self.timings: Dict[str, float] = {}
        self.counters: Dict[str, List[float]] = {}
        self.red: Optional[Dict] = None          # trace reduction
        self.calls = None                        # work per epoch
        self.graphs = None                       # GraphShape per layer

    @property
    def peaks(self) -> Optional[Dict]:
        """The chip's peaks; None on the CPU (rehearsals), an error for
        an accelerator the table does not hold."""
        if self.device["platform"] == "cpu":
            return None
        import peaks
        return peaks.peaks(self.device["kind"])

    @property
    def traced(self) -> bool:
        return bool(self.red and self.red["n_devices"])


def device_info() -> Dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _max_rss_bytes() -> int:
    """This process's peak resident memory on the host (Linux)."""
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def memory_peak_bytes(chips: int) -> int:
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


def build_session(cell: bench.Cell, seed: int):
    from repro.api import DealConfig, Session
    cfg = DealConfig.from_dict(cell.config["deal"])
    cfg.graph.seed = seed
    cfg.refresh.sample_seed = seed
    s = Session.build(cfg)
    got = getattr(s.executor, "name", type(s.executor).__name__)
    if got != cfg.executor.name:
        raise SystemExit(f"asked for executor {cfg.executor.name!r}, "
                         f"got {got!r}")
    return s


def _compare(got: Dict, want, viol: int, limits: Dict) -> Dict[str, Dict]:
    import reference as ref
    res = {"graph_violations": viol}
    if 0 in got:
        res["feature_rows_wrong"] = int(
            (got[0] != want[0]).any(axis=1).sum())
    res["max_rel_err"] = max(ref.rel_err(g, want[lvl])
                             for lvl, g in got.items() if lvl > 0)
    return {k: {"value": v, "limit": limits[k]} for k, v in res.items()}


def check(out: Dict, model, params, limits: Dict,
          control: bool = False) -> Tuple[Dict, Optional[Dict]]:
    """Compare what the window produced (a job's ``outputs()``: the
    program's rows of some levels of every node, the layer graphs, the
    features and the edge list) with the plain reference of ``model``
    (a model file), each number beside its limit.  With ``control`` the
    reference itself, its matmuls one precision step lower, stands in
    the program's place: the first result is then the control's, the
    second the program's."""
    import reference as ref
    viol = sum(ref.graph_violations(nbr, mask, out["src"], out["dst"])
               for nbr, mask in out["graphs"])
    want = ref.forward(model, params, out["X"], out["graphs"])
    program = _compare(out["levels"], want, viol, limits)
    if not control:
        return program, None
    low = ref.forward(model, params, out["X"], out["graphs"],
                      matmul="bf16x3")
    return _compare({lvl: low[lvl] for lvl in out["levels"]}, want, viol,
                    limits), program


def run(cell: bench.Cell, seed: int, seconds: float, trace: bool,
        root: Path = bench.ROOT, control: bool = False) -> Dict:
    """Set-up, window, metrics and comparison of one run; returns the
    result object.  ``control`` puts the comparison's control in the
    program's place (``control.py``)."""
    import jax

    import tracereduce
    import work
    from repro.runtime import enable_compile_cache
    cache = enable_compile_cache()
    clock = CompileClock()
    device = device_info()
    r = Run(cell, device)
    log(f"[device] {device}; jax {jax.__version__}; compile cache {cache}")

    s = build_session(cell, seed)
    r.timings = dict(s.timings)
    m = s.cfg.model
    r.model_cfg = dataclasses.asdict(m)
    X, params = r.model.make_inputs(seed, s.n_nodes, r.model_cfg)
    s.X, s.params = X, params
    log(f"[setup] {cell.config_name}: n_nodes={s.n_nodes} "
        f"n_edges={s.graph.n_edges} model={m.name} heads={m.heads} "
        f"d={m.d_feature} executor={s.cfg.executor.name} "
        f"build={r.timings}")
    spans = Spans()
    kind = bench.load_job(cell)
    job = kind.Job(s, cell.traffic, seed, spans)
    r.unit = kind.UNIT
    for note in job.warm(clock):
        log(f"[warm] {note}")
    r.graphs = [work.GraphShape.of(lg.nbr, lg.mask) for lg in s.layer_graphs]
    r.calls = r.model.epoch_calls(r.graphs, r.model_cfg)
    r.compile_setup_s = clock.secs
    compiles0, compile_s0 = clock.count, clock.secs

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(trace_dir, profiler_options=_trace_options())
        spans.tracing = True
    r.setup_s = process_age_s()
    spans.done.clear()
    steps = []
    with spans("window"):
        t0 = t_last = time.perf_counter()
        while t_last - t0 < seconds:
            job.step()
            r.units += 1
            steps.append(time.perf_counter() - t_last)
            t_last += steps[-1]
    if trace:
        jax.profiler.stop_trace()
        spans.tracing = False
    r.unit_s = (t_last - t0) / r.units
    r.counters = job.counters
    log(f"[window] {r.units} x {r.unit} in {t_last - t0:.6f} s; "
        f"compiles_in_window={clock.count - compiles0} "
        f"({clock.secs - compile_s0:.6f} s); setup_s={r.setup_s:.6f} "
        f"(compile {r.compile_setup_s:.6f} s)")
    slow = sorted(range(len(steps)), key=steps.__getitem__)
    log(f"[window] {r.unit} seconds: min {steps[slow[0]]:.6f} median "
        f"{steps[slow[len(slow) // 2]]:.6f}; slowest "
        + ", ".join(f"#{i} {steps[i]:.6f}" + "".join(
            f" {k}={v[i]:g}" for k, v in job.counters.items())
            for i in slow[:-4:-1]))
    peak = memory_peak_bytes(cell.chips)

    result: Dict = {"correct": False, "attempted": r.units, "failed": 0,
                    "metrics": {}, "device": dict(device,
                                                  memory_peak_bytes=peak)}
    if trace:
        try:
            t = tracereduce.load_xplane(tracereduce.find_xplane(trace_dir),
                                        [n for n, _, _ in spans.done])
            r.red = tracereduce.reduce(t)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if r.traced:
            result["device"]["busy_s"] = r.red["busy_s"]
            result["device"]["window_s"] = r.red["window_s"]
            result["breakdown"] = tracereduce.breakdown(r.red)

    for entry in (cell.per_layer if trace else cell.end_to_end):
        v = bench.load_reader(entry, root).read(r)
        if v is not None:
            result["metrics"][entry["name"]] = {"value": v,
                                                "unit": entry["unit"]}

    out = job.outputs()
    del job
    s.close()
    t_ref = time.perf_counter()
    checks, program = check(out, r.model, params, cell.limits, control)
    log(f"[reference] {time.perf_counter() - t_ref:.3f} s after the "
        f"window; process peak RSS {_max_rss_bytes()} B")
    if program is not None:
        result["program_checks"] = program
        for name, c in program.items():
            log(f"[check] program {name} = {c['value']!r} "
                f"(limit {c['limit']!r})")
    result["correct"] = all(c["value"] <= c["limit"]
                            for c in checks.values())
    result["checks"] = checks
    for name, c in checks.items():
        log(f"[check] {name} = {c['value']!r} (limit {c['limit']!r})")
    return result


def _trace_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # host spans only: keep the host fast
    opts.host_tracer_level = 2
    return opts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        log(f"run.py: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
            f"found {len(devs)} x {devs[0].platform}; refusing")
        return 2
    result = run(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
