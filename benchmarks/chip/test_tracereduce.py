"""The trace reduction on a hand-made trace and on a small recorded one
(a traced one-second window of ``gcn-papers100m.epoch`` on a TPU v5e,
``testdata/trace_gcn_epoch.json``), the latter checked against a
brute-force timeline at 100 ns resolution.  Run by path:
``python -m pytest -q benchmarks/chip/test_tracereduce.py``."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import tracereduce as tr  # noqa: E402

RECORDED = HERE / "testdata" / "trace_gcn_epoch.json"


def hand_trace():
    ops = [("a", "m1", 0.0, 10.0), ("b", "m1", 5.0, 20.0),
           ("c", "m2", 30.0, 40.0), ("all-reduce", "m3", 44.0, 48.0),
           ("d", "m3", 47.0, 49.0)]
    return tr.Trace(
        ops={"/device:TPU:0": ops},
        modules={"/device:TPU:0": [("m1", 0.0, 20.0), ("m2", 30.0, 40.0),
                                   ("m3", 44.0, 49.0)]},
        spans=[("window", 0.0, 50.0), ("epoch", 0.0, 25.0),
               ("refresh", 25.0, 50.0)],
        host=[("X", 28.0, 35.0)])


def test_hand_trace():
    red = tr.reduce(hand_trace())
    assert red["window_s"] == pytest.approx(50e-9)
    # busy: [0, 20] + [30, 40] + [44, 49]
    assert red["busy_s"] == pytest.approx(35e-9)
    assert red["idle_share"] == pytest.approx(0.3)
    assert red["op_s"]["m1/b"] == pytest.approx(15e-9)
    assert red["module_s"] == pytest.approx(
        {"m1": 20e-9, "m2": 10e-9, "m3": 5e-9})
    # the all-reduce [44, 48] overlaps d on [47, 48]
    assert red["collective_exposed_s"] == pytest.approx(3e-9)
    gaps = {k: v[0] for k, v in red["gaps"].items()}
    # idle: [20, 30] = epoch 5, refresh 3, refresh/X 2; [40, 44] and
    # [49, 50] = refresh
    assert gaps == pytest.approx({"epoch": 5e-9, "refresh": 8e-9,
                                  "refresh/X": 2e-9})
    assert red["gaps"]["epoch"][1] == 1          # [20, 30] counts here


def test_nested_labels():
    segs = tr.label_segments([("outer", 0, 100), ("inner", 10, 20)], 0, 100,
                             host=[("ev", 15, 30)])
    assert segs == [(0, 10, "outer"), (10, 15, "inner"),
                    (15, 20, "inner/ev"), (20, 30, "outer/ev"),
                    (30, 100, "outer")]


def brute(trace, res=100.0):
    """Busy time and per-label idle time on a rasterized timeline."""
    lo, hi = tr.window(trace)
    t = lo + (np.arange(int((hi - lo) / res)) + 0.5) * res
    busy = np.zeros(t.size, bool)
    for rows in trace.ops.values():
        for _, _, s, e in rows:
            busy[np.searchsorted(t, s):np.searchsorted(t, e)] = True
    names = ["(none)"]
    label = np.zeros(t.size, np.int16)
    for name, s, e in sorted(trace.spans, key=lambda sp: sp[1] - sp[2]):
        if name == "window":
            continue
        if name not in names:
            names.append(name)
        i0, i1 = np.searchsorted(t, s), np.searchsorted(t, e)
        # widest first, so an inner span overwrites its outer one
        label[i0:i1] = names.index(name)
    return (busy.sum() * res * 1e-9, t.size * res * 1e-9, busy,
            np.array(names, object)[label])


def test_recorded_trace_against_brute_force():
    trace = tr.load_json(str(RECORDED))
    assert len(trace.ops) == 1 and trace.host
    red = tr.reduce(trace)
    busy_s, window_s, busy, label = brute(trace)
    assert red["window_s"] == pytest.approx(window_s, abs=2e-7)
    assert red["busy_s"] == pytest.approx(busy_s, rel=1e-3)
    assert 0.0 < red["idle_share"] < 1.0
    # idle time by harness span (the host-event part of a label dropped)
    got = {}
    for k, (sec, _, _) in red["gaps"].items():
        outer = k.split("/", 1)[0]
        got[outer] = got.get(outer, 0.0) + sec
    want = {lab: (~busy & (label == lab)).sum() * 100e-9
            for lab in set(label[~busy])}
    assert got.keys() == want.keys()
    for lab in want:
        assert got[lab] == pytest.approx(want[lab], rel=1e-3, abs=1e-6)
    # kernel time by module: the clipped sum of its runs
    lo, hi = tr.window(trace)
    spmm = sum(min(e, hi) - max(s, lo)
               for rows in trace.modules.values() for m, s, e in rows
               if m == "spmm" and min(e, hi) > max(s, lo)) * 1e-9
    assert red["module_s"]["spmm"] == pytest.approx(spmm)
    assert red["module_s"]["spmm"] > 0.5 * red["busy_s"]
    # every op sits inside a module run
    assert all(m for rows in trace.ops.values() for _, m, _, _ in rows)


def test_breakdown_shape():
    red = tr.reduce(tr.load_json(str(RECORDED)))
    b = tr.breakdown(red)
    assert set(b) == {"device_ops", "idle_gaps"}
    for key in b:
        assert 1 <= len(b[key]) <= 10
        assert all(isinstance(n, str) and s >= 0 for n, s in b[key])
    assert b["device_ops"][0][0] == "spmm/spmm"
