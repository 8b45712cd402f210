"""The four-chip cell's two readers (``metrics/dist_gat_attention_roofline
.py``, ``metrics/collective_exposed_share.epoch.py``) on a hand-made
two-device trace whose numbers are known, and on runs with nothing to
read.  Run by path:
``python -m pytest -q benchmarks/chip/test_mesh_metrics.py``."""
from __future__ import annotations

import json
import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench  # noqa: E402
import peaks  # noqa: E402
import tracereduce as tr  # noqa: E402
import work  # noqa: E402

CELL = "gat4-papers100m-2x2.epoch"
NAMES = ("dist_gat_attention_roofline", "collective_exposed_share.epoch")


def reader(name):
    spec = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
    e, = [m for m in spec["per_layer"] if m["name"] == name]
    assert e["workloads"] == [CELL]
    assert e["layer"] == "Collectives: core/primitives.py"
    return bench.load_reader(e)


def hand_trace():
    """Window [0, 1000] ns on two devices.  Device 0: the GEMM's
    all-to-all [100, 200] alone, the attention [300, 500] with its
    collective-permute [300, 350] under a fusion [300, 340] (10 ns
    exposed).  Device 1: the all-to-all [100, 160] alone, the attention
    [300, 400] with no collective."""
    d0 = [("all-to-all", "dist_gemm", 100.0, 200.0),
          ("collective-permute", "dist_gat_attention", 300.0, 350.0),
          ("fusion", "dist_gat_attention", 300.0, 340.0),
          ("fusion.1", "dist_gat_attention", 350.0, 500.0)]
    d1 = [("all-to-all", "dist_gemm", 100.0, 160.0),
          ("fusion", "dist_gat_attention", 300.0, 400.0)]
    m0 = [("dist_gemm", 100.0, 200.0), ("dist_gat_attention", 300.0, 500.0)]
    m1 = [("dist_gemm", 100.0, 160.0), ("dist_gat_attention", 300.0, 400.0)]
    return tr.Trace(ops={"/device:TPU:0": d0, "/device:TPU:1": d1},
                    modules={"/device:TPU:0": m0, "/device:TPU:1": m1},
                    spans=[("window", 0.0, 1000.0)])


def gat_run(red, units=2):
    g = work.GraphShape(n=1024, fanout=8, edges=4096, src_rows=900,
                        active=1000)
    model = {"d_feature": 128, "heads": 4, "n_layers": 2}
    gat = bench.load_model(bench.load_cell(CELL))
    return types.SimpleNamespace(
        unit="epoch", red=red, traced=bool(red and red["n_devices"]),
        units=units, calls=gat.epoch_calls([g, g], model),
        peaks=peaks.peaks("TPU v5 lite"))


def test_hand_trace():
    run = gat_run(tr.reduce(hand_trace()))
    # exposed: (100 + 10) on device 0, 60 on device 1 -> 85 ns average
    got = reader("collective_exposed_share.epoch").read(run)
    assert got == pytest.approx(100 * 85 / 1000)
    # the module ran 200 ns on device 0 and 100 on device 1: 150e-9 s
    # average; each device's share of the logical least time is 1/2
    least = work.least_s(run.calls, "gat_attention", 197e12, 819e9) * 2
    got = reader("dist_gat_attention_roofline").read(run)
    assert got == pytest.approx(100 * least / 2 / 150e-9)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read(name):
    untraced = gat_run(None)
    assert reader(name).read(untraced) is None
    # a one-chip trace (the parent's program, or a Pallas cell) has no
    # dist_gat_attention module to time
    one = tr.Trace(ops={"/device:TPU:0": [("spmm", "spmm", 0.0, 10.0)]},
                   modules={"/device:TPU:0": [("spmm", 0.0, 10.0)]},
                   spans=[("window", 0.0, 100.0)])
    run = gat_run(tr.reduce(one))
    if name == "dist_gat_attention_roofline":
        assert reader(name).read(run) is None
    else:
        assert reader(name).read(run) == 0.0
