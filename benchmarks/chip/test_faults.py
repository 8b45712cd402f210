"""The comparison that decides ``correct`` fails what it must (run by
path, on the CPU: ``python -m pytest -q benchmarks/chip/test_faults.py``).

Each case runs for every model file under ``models/``, in the first
cell whose configuration names it.

* The control: a whole run with the reference, its matmuls one
  precision step below the configuration's (bf16x3 for float32 at
  HIGHEST), in the program's place comes out not correct through the
  run's own comparison, while a sound run comes out correct.
* Each fault the cells can have, planted under the timed path of a whole
  tiny run (the harness's look for a chip skipped), turns ``correct``
  false: an answer altered where it is produced, and half of the rows
  left out.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench  # noqa: E402
import control  # noqa: E402
import run as runmod  # noqa: E402
from test_rehearsal import ROOT, SPEC, tiny_copy  # noqa: E402

SEED = 2**31 + 977
MODELS = sorted(p.stem for p in (HERE / "models").glob("*.py"))


def _cell_of(model: str, traffic: str = "") -> str:
    """The first cell whose configuration runs ``model`` and whose mix's
    name starts with ``traffic``."""
    for w in SPEC["workloads"]:
        c = next(c for c in SPEC["configs"] if c["name"] == w["config"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        if (cfg["deal"]["model"]["name"] == model
                and w["traffic"].startswith(traffic)):
            return w["name"]
    raise LookupError(f"no cell of BENCHMARK.json runs model {model!r} "
                      f"under a mix {traffic}*")


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_copy(tmp_path_factory.mktemp("bench"))


def run_tiny(root, name):
    cell = bench.load_cell(name, root)
    return runmod.run(cell, SEED, 0.2, False, root=root)


@pytest.mark.parametrize("model", MODELS)
def test_control_fails_the_limit(tiny, model):
    res = control.control_run(bench.load_cell(_cell_of(model), tiny), SEED,
                              root=tiny)
    assert not res["correct"]
    c = res["checks"]["max_rel_err"]
    assert c["value"] > c["limit"]
    assert res["checks"]["graph_violations"]["value"] == 0


@pytest.mark.parametrize("model", MODELS)
def test_sound_run_passes(tiny, model):
    assert run_tiny(tiny, _cell_of(model))["correct"]


def _alter_first_row(out):
    out = np.array(out)
    out[0] += 1e-3 * np.abs(out).max()
    return out


@pytest.mark.parametrize("model", MODELS)
def test_epoch_answer_altered(tiny, model, monkeypatch):
    import repro.core.ops as ops
    real = ops.run_model
    monkeypatch.setattr(ops, "run_model",
                        lambda *a, **k: _alter_first_row(real(*a, **k)))
    res = run_tiny(tiny, _cell_of(model, "epoch"))
    assert not res["correct"]
    assert res["checks"]["max_rel_err"]["value"] > 1e-4


@pytest.mark.parametrize("model", MODELS)
def test_epoch_half_the_rows_left_out(tiny, model, monkeypatch):
    import repro.core.ops as ops
    real = ops.run_model

    def half(*a, **k):
        H = np.array(real(*a, **k))
        H[H.shape[0] // 2:] = 0.0
        return H
    monkeypatch.setattr(ops, "run_model", half)
    assert not run_tiny(tiny, _cell_of(model, "epoch"))["correct"]
