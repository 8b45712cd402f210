"""CPU rehearsal of the chip benchmark (not collected by the tier-1 run;
run it by path):

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/test_rehearsal.py

Each cell of ``BENCHMARK.json`` runs at a tiny scale through the
harness's own functions (Pallas in interpret mode), with and without a
trace; ``run.py`` itself refuses without a TPU; and a configuration, a
job kind, a traffic mix, a metric and a model added as new files plus
new entries, in a copy of the benchmark, are found by name with no edit
to any existing file.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import bench  # noqa: E402
import run as runmod  # noqa: E402

TINY_SCALE = 1 / 64       # 256 nodes of the papers100M stand-in
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def tiny_copy(tmp: Path) -> Path:
    """A copy of the benchmark whose configurations run at TINY_SCALE
    on one device."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for c in SPEC["configs"]:
        f = tmp / c["file"]
        d = json.loads(f.read_text())
        d["deal"]["graph"]["scale"] = TINY_SCALE
        d["deal"]["partition"] = {"p": 1, "m": 1}
        if d["deal"]["executor"]["name"] == "dist":
            d["deal"]["executor"]["name"] = "pallas"
        f.write_text(json.dumps(d))
    return tmp


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_copy(tmp_path_factory.mktemp("bench"))


def run_cell(root: Path, name: str, trace: bool, seed: int = 2**31 + 11):
    cell = bench.load_cell(name, root)
    return cell, runmod.run(cell, seed, 0.2, trace, root=root)


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_tiny(tiny, name, trace):
    cell, res = run_cell(tiny, name, trace)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]
    want = cell.per_layer if trace else cell.end_to_end
    got = res["metrics"]
    assert set(got) <= {m["name"] for m in want}
    if not trace:
        # every end-to-end metric is a host clock: always there
        assert set(got) == {m["name"] for m in want}
        assert all(v["value"] > 0 for v in got.values())
    else:
        # device metrics have no device to read on the CPU
        assert "build_host_s" in got and "compile_s" in got
        assert not any(k.startswith("device_idle") or "roofline" in k
                       for k in got)


def test_run_py_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "refusing" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


NEW_KIND = '''"""Job kind: before each epoch, rewrite some feature rows."""
import numpy as np

UNIT = "epoch"


class Job:
    def __init__(self, session, traffic, seed, spans):
        self.s, self.spans, self.H = session, spans, None
        session.X = np.array(session.X)          # a writable copy
        self.rows = int(traffic["rows"])
        self.rng = np.random.default_rng(seed)
        self.counters = {"rows_rewritten": []}

    def _epoch(self):
        s = self.s
        ids = self.rng.choice(s.n_nodes, self.rows, replace=False)
        s.X[ids] = self.rng.standard_normal((self.rows, s.X.shape[1]),
                                            dtype=np.float32)
        s._H = None
        with self.spans("epoch"):
            return s.infer_all()

    def warm(self, compiles):
        self._epoch()
        return []

    def step(self):
        self.H = self._epoch()
        self.counters["rows_rewritten"].append(float(self.rows))

    def outputs(self):
        s = self.s
        return {"levels": {s.cfg.model.n_layers: self.H},
                "graphs": [(g.nbr, g.mask) for g in s.layer_graphs],
                "X": s.X.copy(), "src": s.src, "dst": s.dst}
'''


def test_new_files_are_found_by_name(tmp_path):
    """A configuration, a job kind, a traffic mix of that kind and a
    metric, each a new file with a new entry in BENCHMARK.json; no
    existing file is edited."""
    root = tiny_copy(tmp_path)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    bdir = root / SPEC["paths"][0]
    cfg = json.loads((bdir / "configs" / "gcn-papers100m.json").read_text())
    cfg["deal"]["model"]["n_layers"] = 2
    cfg["limits"]["fresh-rows"] = cfg["limits"]["epoch"]
    (bdir / "configs" / "throwaway.json").write_text(json.dumps(cfg))
    (bdir / "jobs" / "fresh-rows.py").write_text(NEW_KIND)
    (bdir / "traffic" / "fresh-rows-16.json").write_text(
        '{"job": "fresh-rows", "rows": 16}')
    (bdir / "metrics" / "epochs_done.py").write_text(
        'UNIT = "epochs"\nLAYER = "Model step"\nMOVES = "epoch_s"\n\n\n'
        'def read(run):\n    return float(run.units)\n')
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "throwaway", "source": "https://example.org/throwaway",
        "file": f"{SPEC['paths'][0]}/configs/throwaway.json",
        "reduced": [], "why": "a test"})
    spec["workloads"].append({
        "name": "throwaway.fresh-rows-16", "config": "throwaway",
        "traffic": "fresh-rows-16", "chips": 1, "why": "a test"})
    spec["per_layer"].append({
        "name": "epochs_done", "unit": "epochs", "better": "higher",
        "source": "host_clock", "layer": "Model step",
        "moves": "epoch_s", "workloads": ["throwaway.fresh-rows-16"]})
    spec["end_to_end"][0]["workloads"].append("throwaway.fresh-rows-16")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell, res = run_cell(root, "throwaway.fresh-rows-16", trace=True)
    assert cell.config["deal"]["model"]["n_layers"] == 2
    # correct only where the reference starts from the rewritten rows
    assert res["correct"], res["checks"]
    assert res["metrics"]["epochs_done"]["value"] == res["attempted"]
    changed = [p for p, b in before.items()
               if p.name != "BENCHMARK.json" and p.read_bytes() != b]
    assert changed == []


SAGE = '''"""GraphSAGE with mean aggregation, as the program registers it:

  agg[i] = sum_f m[i,f] / max(sum_f m[i,f], 1) * h[nbr[i,f]]
  h'[i] = h[i] W_self + agg[i] W_nbr, relu between layers
"""
import jax
import jax.numpy as jnp

import reference as ref
import work

GATHERED = (False, True)        # h W_self at own rows, h at neighbour ids


def make_inputs(seed, n, model):
    layers = model["n_layers"]
    X, W = ref.draw(seed, n=n, d=model["d_feature"], layers=layers, per=2)
    return X, {"layers": [{"w_self": W[l, 0], "w_nbr": W[l, 1]}
                          for l in range(layers)]}


def layer(params, l):
    return params["layers"][l]


def operands(h, p, matmul):
    return ref.dot(h, p["w_self"], matmul), h


def block(p, own, hn, mask, matmul):
    m = mask.astype(jnp.float32)
    w = m / jnp.maximum(m.sum(axis=1, keepdims=True), 1.0)
    agg = (w[..., None] * hn).sum(axis=1)
    return own + ref.dot(agg, p["w_nbr"], matmul)


activation = jax.nn.relu


def epoch_calls(graphs, model):
    d = model["d_feature"]
    calls = []
    for g in graphs:
        calls += [("spmm", work.spmm(g, d))]
        calls += [("gemm", work.gemm(g.n, d, d))] * 2
    return calls


def epoch_min_bytes(graphs, model):
    return work.fused_epoch_bytes(graphs, model["d_feature"], weights=2)
'''


def test_new_model_is_a_file(tmp_path):
    """A model (the program's registered ``sage``) as a new model file,
    with a new configuration and cell; no existing file is edited.  The
    cell runs correct, its control does not."""
    import control
    root = tiny_copy(tmp_path)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    bdir = root / SPEC["paths"][0]
    (bdir / "models" / "sage.py").write_text(SAGE)
    cfg = json.loads((bdir / "configs" / "gcn-papers100m.json").read_text())
    cfg["deal"]["model"]["name"] = "sage"
    (bdir / "configs" / "sage-tiny.json").write_text(json.dumps(cfg))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "sage-tiny", "source": "https://example.org/sage",
        "file": f"{SPEC['paths'][0]}/configs/sage-tiny.json",
        "reduced": [], "why": "a test"})
    spec["workloads"].append({
        "name": "sage-tiny.epoch", "config": "sage-tiny", "traffic": "epoch",
        "chips": 1, "why": "a test"})
    spec["end_to_end"][0]["workloads"].append("sage-tiny.epoch")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell, res = run_cell(root, "sage-tiny.epoch", trace=False)
    assert bench.load_model(cell).__doc__.startswith("GraphSAGE")
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    low = control.control_run(cell, 2**31 + 11, root=root)
    assert not low["correct"], low["checks"]
    assert low["program_checks"]["max_rel_err"]["value"] <= (
        low["program_checks"]["max_rel_err"]["limit"])
    changed = [p for p, b in before.items()
               if p.name != "BENCHMARK.json" and p.read_bytes() != b]
    assert changed == []


def test_missing_model_file_is_named(tmp_path):
    root = tiny_copy(tmp_path)
    cell = bench.load_cell(SPEC["workloads"][0]["name"], root)
    cell.config["deal"]["model"]["name"] = "nosuchmodel"
    with pytest.raises(ValueError, match="nosuchmodel.py"):
        bench.load_model(cell)
