"""The three idle-phase readers (``metrics/idle_{staging,dispatch,fetch}
_share.epoch.py``) on a hand-made trace whose phase shares are known, on
a trace of a program that writes no spans, and on a recorded one: a
traced one-second window of ``gcn-papers100m.epoch`` on a TPU v5e with
the program's ``repro.obs`` spans in it
(``testdata/trace_gcn_epoch_spans.json``).  Run by path:
``python -m pytest -q benchmarks/chip/test_idle_phases.py``."""
from __future__ import annotations

import json
import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench  # noqa: E402
import tracereduce as tr  # noqa: E402

RECORDED = HERE / "testdata" / "trace_gcn_epoch_spans.json"
NO_SPANS = HERE / "testdata" / "trace_gcn_epoch.json"
PHASES = ("staging", "dispatch", "fetch")
CELLS = ["gat4-papers100m.epoch", "gcn-papers100m.epoch"]


def entry(phase):
    spec = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
    name = f"idle_{phase}_share.epoch"
    e, = [m for m in spec["per_layer"] if m["name"] == name]
    return e


def reader(phase):
    return bench.load_reader(entry(phase))


def as_run(red, unit="epoch"):
    return types.SimpleNamespace(unit=unit, red=red,
                                 traced=bool(red and red["n_devices"]))


def shares(trace):
    run = as_run(tr.reduce(trace))
    return run, {p: reader(p).read(run) for p in PHASES}


def hand_trace():
    """Window [0, 1000] ns.  Device busy [300, 310], [400, 700],
    [720, 800]; the host in one epoch's phases (see the sums below)."""
    ops = [("fusion", "dot", 300.0, 310.0), ("spmm", "spmm", 400.0, 700.0),
           ("relu", "relu", 720.0, 800.0)]
    mods = [("dot", 300.0, 310.0), ("spmm", 400.0, 700.0),
            ("relu", 720.0, 800.0)]
    host = [("session.infer_all", 20.0, 980.0),
            ("infer.bind", 30.0, 130.0),
            ("DevicePutWithSharding", 40.0, 60.0),
            ("infer.forward", 140.0, 700.0),
            ("model.prepare", 150.0, 250.0), ("shard_args", 160.0, 240.0),
            ("DevicePutWithSharding", 170.0, 230.0),
            ("ops.gemm", 260.0, 300.0), ("PjitFunction(dot)", 265.0, 280.0),
            ("ops.spmm", 310.0, 400.0), ("infer.mean_w", 315.0, 360.0),
            ("DevicePut", 362.0, 368.0),
            ("PjitFunction(spmm)", 370.0, 390.0),
            ("ops.activation", 410.0, 420.0),
            ("infer.fetch", 700.0, 900.0),
            ("np.asarray(jax.Array)", 705.0, 895.0),
            ("infer.check", 900.0, 960.0)]
    return tr.Trace(ops={"/device:TPU:0": ops},
                    modules={"/device:TPU:0": mods},
                    spans=[("window", 0.0, 1000.0), ("reset", 0.0, 10.0),
                           ("epoch", 10.0, 990.0)],
                    host=host)


@pytest.mark.parametrize("phase", PHASES)
def test_entry_matches_reader(phase):
    e = entry(phase)
    assert (e["unit"], e["better"], e["source"], e["moves"]) == \
        ("%", "lower", "program_span", "epoch_s")
    assert e["workloads"] == CELLS
    assert reader(phase).LAYER == e["layer"]


def test_hand_trace_shares():
    run, got = shares(hand_trace())
    # staging: bind 10 + 20 (DevicePutWithSharding) + 70; prepare 10 +
    # 10 + 60 + 10 + 10 (shard_args / DevicePutWithSharding inside it);
    # mean_w 45
    # dispatch: forward 10 + 10; gemm 5 + 15 + 20; spmm 5 + 2 + 6
    # (a plain DevicePut, from an op's dispatch) + 2 + 20 + 10
    # fetch: fetch 5 + 15 + 95 + 5 (np.asarray inside it); check 60
    assert got == pytest.approx({"staging": 24.5, "dispatch": 10.5,
                                 "fetch": 18.0})
    # the rest of the 610 ns idle: reset 10, bare epoch 10 + 10,
    # bare session.infer_all 10 + 10 + 20, (none) 10
    assert 100 * run.red["idle_share"] == pytest.approx(61.0)
    assert sum(got.values()) == pytest.approx(61.0 - 8.0)


def test_silent_where_the_program_writes_no_spans():
    # the same cell traced before the program's spans reached the trace
    _, got = shares(tr.load_json(str(NO_SPANS)))
    assert got == {p: None for p in PHASES}


def test_silent_off_an_epoch_or_without_a_trace():
    red = tr.reduce(hand_trace())
    for p in PHASES:
        assert reader(p).read(as_run(red, unit="refresh")) is None
        assert reader(p).read(as_run(None)) is None


def test_recorded_trace_splits_the_idle_time():
    run, got = shares(tr.load_json(str(RECORDED)))
    assert all(v is not None and v > 0 for v in got.values()), got
    idle = 100 * run.red["idle_share"]
    assert sum(got.values()) <= idle
    assert sum(got.values()) >= 0.85 * idle
    # the host no longer hides in the harness's bare ``epoch`` span
    bare = run.red["gaps"].get("epoch", [0.0])[0]
    assert bare < 0.10 * (idle / 100) * run.red["window_s"]


def counts(mod, name):
    return name in mod.NAMES or name.startswith(mod.PREFIXES)


@pytest.mark.parametrize("phase", PHASES)
def test_recorded_runtime_events_sit_in_their_phase(phase):
    """Each runtime event a reader counts lies, in the recorded trace,
    inside one of that reader's own program spans (its innermost
    enclosing program span is one of them), and no runtime event that
    another reader counts does."""
    mod = reader(phase)
    others = [reader(p) for p in PHASES if p != phase]
    host = tr.load_json(str(RECORDED)).host
    program = [h for h in host if h[0].startswith(mod.PROGRAM)]
    seen = 0
    for name, s, e in host:
        if name.startswith(mod.PROGRAM):
            continue
        holders = [p for p in program if p[1] <= s and e <= p[2]]
        if not holders:
            continue
        inner = min(holders, key=lambda p: p[2] - p[1])[0]
        if counts(mod, name):
            assert counts(mod, inner), (name, inner)
            seen += 1
        elif counts(mod, inner):
            assert not any(counts(o, name) for o in others), (name, inner)
    assert seen > 0
