"""Operation and byte counts from logical shapes, against hand arithmetic
(run by path: ``python -m pytest -q benchmarks/chip/test_work.py``)."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench  # noqa: E402
import peaks  # noqa: E402
import work  # noqa: E402

# 4 target rows, fanout 2: 5 sampled edges reading source rows {0, 1, 2};
# rows 0, 1 and 3 have an edge, row 2 has none
NBR = np.array([[1, 2], [0, 0], [3, 3], [1, 0]], np.int32)
MASK = np.array([[1, 1], [1, 0], [0, 0], [1, 1]], bool)


@pytest.fixture
def g():
    return work.GraphShape.of(NBR, MASK)


def model_file(name):
    return bench._load_module(HERE / "models" / f"{name}.py",
                              "model_" + name)


def test_graph_shape(g):
    assert (g.n, g.fanout, g.edges, g.src_rows, g.active) == (4, 2, 5, 3, 3)


def test_gemm():
    w = work.gemm(4, 128, 128)
    assert w.flops == 2 * 4 * 128 * 128
    assert w.bytes == 4 * (4 * 128 + 128 * 128 + 4 * 128)


def test_spmm(g):
    w = work.spmm(g, 4)
    assert w.flops == 2 * 5 * 4
    # 3 source rows once, 5 ids, 8 mask bytes, 5 weights, 4x4 output
    assert w.bytes == 4 * 3 * 4 + 4 * 5 + 8 + 4 * 5 + 4 * 4 * 4


def test_gat_attention(g):
    w = work.gat_attention(g, 128, 4)
    assert w.flops == 2 * 5 * 128
    # q of 3 active rows, k of 3 read rows, ids, mask, (4, 2, 4) scores
    assert w.bytes == 4 * 3 * 128 * 2 + 4 * 5 + 8 + 4 * 4 * 2 * 4


def test_padded_head_is_not_counted(g):
    """GAT's attend counts as four logical spmm calls of a 32-wide head
    each, however the kernel pads or fuses them; the count stays at 32
    per head."""
    calls = model_file("gat").epoch_calls(
        [g], {"d_feature": 128, "heads": 4})
    heads = [w for k, w in calls if k == "spmm"]
    assert len(heads) == 4
    assert all(w == work.spmm(g, 32) for w in heads)
    total = sum(w.flops for w in heads)
    assert total == 2 * 5 * 128                       # not 4 x 2*5*128
    padded = work.spmm(g, 128)
    assert total == padded.flops
    assert 4 * work.spmm(g, 32).flops < 4 * padded.flops


def test_epoch_calls_gcn(g):
    calls = model_file("gcn").epoch_calls(
        [g, g], {"d_feature": 4, "heads": 1})
    assert [k for k, _ in calls] == ["gemm", "spmm"] * 2
    assert work.epoch_flops(calls) == 2 * (2 * 4 * 4 * 4 + 2 * 5 * 4)


def test_epoch_min_bytes(g):
    # X read + final write, two graphs (ids + mask) and weights, one
    # intermediate written and read back
    want = 4 * 4 * 4 * 2 + 2 * (4 * 5 + 8 + 4 * 4 * 4) + 2 * 4 * 4 * 4
    assert model_file("gcn").epoch_min_bytes([g, g],
                                             {"d_feature": 4}) == want


# Three layer graphs of the cells' size (1,736,704 rows, fanout 8), and
# what each model's epoch ran and moved, as the harness counted them
# before the counts moved into the model files.
CELL_GRAPHS = [work.GraphShape(n=1736704, fanout=8, edges=13470000 + 1000 * i,
                               src_rows=1650000 + 17 * i,
                               active=1730000 + 3 * i) for i in range(3)]
GEMM = ("gemm", 56908316672, 1778450432)
COUNTED = {
    "gcn": ([GEMM, ("spmm", 3448320000, 1855646080),
             GEMM, ("spmm", 3448576000, 1855662784),
             GEMM, ("spmm", 3448832000, 1855679488)], 5538684192),
    "gat": ([GEMM] * 3 + [("gat_attention", 3448320000, 2020631744)]
            + [("spmm", 862080000, 555151744)] * 4
            + [GEMM] * 3 + [("gat_attention", 3448576000, 2020645984)]
            + [("spmm", 862144000, 555161920)] * 4
            + [GEMM] * 3 + [("gat_attention", 3448832000, 2020660224)]
            + [("spmm", 862208000, 555172096)] * 4, 5539077408),
}
CELL_MODEL = {"gcn": {"name": "gcn", "n_layers": 3, "d_feature": 128,
                      "heads": 1},
              "gat": {"name": "gat", "n_layers": 3, "d_feature": 128,
                      "heads": 4}}


@pytest.mark.parametrize("name", sorted(COUNTED))
def test_model_counts_at_the_cells_shapes(name):
    calls, least_bytes = COUNTED[name]
    m = model_file(name)
    got = m.epoch_calls(CELL_GRAPHS, CELL_MODEL[name])
    assert [(k, w.flops, w.bytes) for k, w in got] == calls
    assert m.epoch_min_bytes(CELL_GRAPHS, CELL_MODEL[name]) == least_bytes


def test_least_time_is_the_larger_bound():
    w = work.Work(flops=1e12, bytes=819e9)
    assert w.least_s(197e12, 819e9) == 1.0
    w = work.Work(flops=197e12, bytes=1.0)
    assert w.least_s(197e12, 819e9) == 1.0


def test_peaks_table():
    p = peaks.peaks("TPU v5 lite")
    assert (p["flops_bf16"], p["hbm_bytes_s"], p["hbm_bytes"]) == (
        197e12, 819e9, 16e9)
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
