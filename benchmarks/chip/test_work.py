"""Operation and byte counts from logical shapes, against hand arithmetic
(run by path: ``python -m pytest -q benchmarks/chip/test_work.py``)."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import peaks  # noqa: E402
import work  # noqa: E402

# 4 target rows, fanout 2: 5 sampled edges reading source rows {0, 1, 2};
# rows 0, 1 and 3 have an edge, row 2 has none
NBR = np.array([[1, 2], [0, 0], [3, 3], [1, 0]], np.int32)
MASK = np.array([[1, 1], [1, 0], [0, 0], [1, 1]], bool)


@pytest.fixture
def g():
    return work.GraphShape.of(NBR, MASK)


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
    """GAT's 32-wide heads run as four spmm calls, each padded to a
    128-lane tile by the kernel; the count stays at 32 per head."""
    calls = work.epoch_calls("gat", [g], d=128, heads=4)
    heads = [w for k, w in calls if k == "spmm"]
    assert len(heads) == 4
    assert all(w == work.spmm(g, 32) for w in heads)
    total = sum(w.flops for w in heads)
    assert total == 2 * 5 * 128                       # not 4 x 2*5*128
    padded = work.spmm(g, 128)
    assert total == padded.flops
    assert 4 * work.spmm(g, 32).flops < 4 * padded.flops


def test_epoch_calls_gcn(g):
    calls = work.epoch_calls("gcn", [g, g], d=4, heads=1)
    assert [k for k, _ in calls] == ["gemm", "spmm"] * 2
    assert work.epoch_flops(calls) == 2 * (2 * 4 * 4 * 4 + 2 * 5 * 4)


def test_epoch_min_bytes(g):
    # X read + final write, two graphs (ids + mask) and weights, one
    # intermediate written and read back
    want = 4 * 4 * 4 * 2 + 2 * (4 * 5 + 8 + 4 * 4 * 4) + 2 * 4 * 4 * 4
    assert work.epoch_min_bytes("gcn", [g, g], 4) == want


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
