"""Finds a cell's pieces by the names in ``BENCHMARK.json``.

  configuration   the file the config entry names (``configs/<name>.json``):
                  the ``DealConfig`` sections under ``deal``, with
                  ``chips``, ``source``, ``reduced``, ``assumed`` and,
                  per job kind, the comparison's ``limits``
  traffic mix     ``traffic/<traffic>.json``: a data file that names its
                  job kind (``"job"``) and that kind's parameters
  job kind        ``jobs/<job>.py``: the generator of one kind of work,
                  with ``UNIT`` and ``Job(session, traffic, seed, spans)``
                  that has ``warm(compiles)``, ``step()``, ``counters``
                  and ``outputs()`` (what the comparison needs)
  model           ``models/<name>.py``, by the configuration's
                  ``deal.model.name``: the inputs drawn from the seed
                  (``make_inputs``), the reference's layer equations
                  (``GATHERED``, ``layer``, ``operands``, ``block``,
                  ``activation``; see ``reference.py``) and the work of
                  one epoch (``epoch_calls``, ``epoch_min_bytes``)
  metric          ``metrics/<name>.py``: a reader with ``UNIT``,
                  ``LAYER``, ``MOVES`` and ``read(run)``, which returns
                  the value or None where it finds nothing to read

A new cell, configuration, mix, job kind, model or metric is a new file
and a new entry; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict
    traffic_name: str
    traffic: Dict
    end_to_end: List[Dict]     # the metrics entries this cell reports
    per_layer: List[Dict]
    bench_dir: Path

    @property
    def limits(self) -> Dict:
        """The comparison's limits for this cell's job kind."""
        return self.config["limits"][self.traffic["job"]]


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _spec(root: Path) -> Dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def load_cell(name: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    spec = _spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    c = configs[w["config"]]
    bench_dir = root / spec["paths"][0]
    return Cell(
        name=name, chips=int(w["chips"]), config_name=c["name"],
        config=json.loads((root / c["file"]).read_text()),
        traffic_name=w["traffic"],
        traffic=json.loads(
            (bench_dir / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)],
        bench_dir=bench_dir)


def _load_module(path: Path, name: str):
    mspec = importlib.util.spec_from_file_location(
        "chipbench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mspec)
    mspec.loader.exec_module(mod)
    return mod


def load_job(cell: Cell):
    """The job-kind module the cell's traffic mix names."""
    kind = cell.traffic["job"]
    path = cell.bench_dir / "jobs" / f"{kind}.py"
    if not path.is_file():
        raise ValueError(f"traffic {cell.traffic_name!r} names job kind "
                         f"{kind!r}, and there is no {path}")
    return _load_module(path, "job_" + kind)


def load_model(cell: Cell):
    """The model module the cell's configuration names."""
    name = cell.config["deal"]["model"]["name"]
    path = cell.bench_dir / "models" / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"config {cell.config_name!r} names model "
                         f"{name!r}, and there is no {path}")
    return _load_module(path, "model_" + name)


def load_reader(metric: Dict, root: Path = ROOT):
    """The reader module of a metric entry; its declared unit, layer and
    moved metric must agree with the entry."""
    root = Path(root)
    path = root / _spec(root)["paths"][0] / "metrics" / f"{metric['name']}.py"
    mod = _load_module(path, "metric_" + metric["name"])
    for key, attr in (("unit", "UNIT"), ("layer", "LAYER"),
                      ("moves", "MOVES")):
        if key in metric and getattr(mod, attr, None) != metric[key]:
            raise ValueError(f"metric {metric['name']}: BENCHMARK.json "
                             f"says {key}={metric[key]!r}, the reader "
                             f"{attr}={getattr(mod, attr, None)!r}")
    return mod
