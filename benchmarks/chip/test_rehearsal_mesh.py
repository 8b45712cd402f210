"""CPU rehearsal of the four-chip cells on four forced host devices
(not collected by the tier-1 run; run it by path):

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/test_rehearsal_mesh.py

Each cell of ``BENCHMARK.json`` that asks for 4 chips runs at a tiny
scale on its own mesh (``deal.partition`` and ``deal.executor`` as the
configuration gives them) through the harness's own functions, in a
child process that sees four CPU devices: the run must be correct
against the model file's reference, and the comparison's control
(``control.py``: the reference at bf16x3 in the program's place) must
not be.
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
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
MESH_CELLS = [w["name"] for w in SPEC["workloads"] if w["chips"] == 4]
TINY_SCALE = 1 / 64       # 256 nodes of the papers100M stand-in
SEED = 2**31 + 4099


def mesh_copy(tmp: Path) -> Path:
    """A copy of the benchmark whose configurations run at TINY_SCALE,
    each on the mesh it states."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for c in SPEC["configs"]:
        f = tmp / c["file"]
        d = json.loads(f.read_text())
        d["deal"]["graph"]["scale"] = TINY_SCALE
        f.write_text(json.dumps(d))
    return tmp


def child(root: Path) -> None:
    """Run each four-chip cell and its control; print one JSON line."""
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    import bench
    import control
    import run as runmod
    out = {}
    for name in MESH_CELLS:
        cell = bench.load_cell(name, root)
        out[name] = {
            "run": runmod.run(cell, SEED, 0.2, False, root=root),
            "control": control.control_run(cell, SEED, root=root)}
    print(json.dumps(out))


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    root = mesh_copy(tmp_path_factory.mktemp("bench"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, __file__, str(root)], cwd=ROOT,
                       env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", MESH_CELLS)
def test_mesh_cell_is_correct(results, name):
    res = results[name]["run"]
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == 4
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    cell_metrics = {m["name"] for m in SPEC["end_to_end"]
                    if "workloads" not in m or name in m["workloads"]}
    assert set(res["metrics"]) == cell_metrics


@pytest.mark.parametrize("name", MESH_CELLS)
def test_mesh_cell_control_is_not(results, name):
    res = results[name]["control"]
    assert not res["correct"], res["checks"]
    c = res["checks"]["max_rel_err"]
    assert c["value"] > c["limit"]
    assert res["checks"]["graph_violations"]["value"] == 0
    prog = res["program_checks"]["max_rel_err"]
    assert prog["value"] <= prog["limit"]


if __name__ == "__main__":
    child(Path(sys.argv[1]))
