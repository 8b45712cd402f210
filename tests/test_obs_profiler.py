"""``repro.obs`` spans in a ``jax.profiler`` trace: with telemetry
disabled a span under an active trace is a falsy ``TraceAnnotation`` of
its exact name, so the all-node epoch's host phases (bindings, mean
weights, feature upload, forward dispatch, copy back, NaN check) land
in the device trace, nested under ``session.infer_all``; outside a trace
a span stays the shared no-op.  No span changes what the device does:
``run_layer`` never syncs under the profiler, and the epoch's output is
bitwise the same with and without a trace.

Every profiler session is opened by a fixture, never at import.
"""
import glob
import os

import jax
import numpy as np
import pytest

from repro import obs
from repro.api import DealConfig, GraphSpec, ModelSpec, Session
from repro.core import ops as core_ops

N_LAYERS = 2
PHASES = ("infer.bind", "infer.mean_w", "model.prepare", "infer.forward",
          "ops.gemm", "ops.spmm", "ops.activation", "infer.fetch",
          "infer.check")


def _cfg(executor="ref", telemetry=False):
    cfg = DealConfig(
        graph=GraphSpec(dataset="rmat", n_nodes=256, avg_degree=8,
                        fanout=4),
        model=ModelSpec(name="gcn", n_layers=N_LAYERS, d_feature=16))
    cfg.executor.name = executor
    cfg.telemetry.enabled = telemetry
    return cfg


@pytest.fixture
def profile(tmp_path):
    """``profile(fn)`` runs ``fn`` under a profiler trace written to a
    fresh directory and returns (fn's result, the trace's host lines as
    lists of (name, start_ns, end_ns))."""
    def run(fn):
        log_dir = tmp_path / f"trace{len(list(tmp_path.iterdir()))}"
        with jax.profiler.trace(str(log_dir)):
            assert obs.profiling()
            out = fn()
        assert not obs.profiling()
        path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        pd = jax.profiler.ProfileData.from_file(path)
        lines = [[(e.name, e.start_ns, e.end_ns) for e in line.events]
                 for plane in pd.planes if plane.name.startswith("/host:")
                 for line in plane.lines]
        return out, lines
    return run


@pytest.fixture
def count_syncs(monkeypatch):
    """Counts ``jax.block_until_ready`` calls made by ``run_layer``."""
    calls = []
    real = core_ops.jax.block_until_ready

    def counting(x):
        calls.append(1)
        return real(x)

    monkeypatch.setattr(core_ops.jax, "block_until_ready", counting)
    return calls


def _epoch(executor="ref", telemetry=False):
    with Session.build(_cfg(executor, telemetry)) as s:
        return s.infer_all().copy()


def _epoch_line(lines):
    """The one host line that holds ``session.infer_all``."""
    mine = [ln for ln in lines
            if any(n == "session.infer_all" for n, _, _ in ln)]
    assert len(mine) == 1, [sorted({n for n, _, _ in ln})[:8]
                            for ln in mine]
    return mine[0]


def test_span_without_profiler_is_the_shared_noop():
    assert not obs.profiling()
    assert obs.span("infer.bind") is obs.NOOP_SPAN
    assert obs.DISABLED.span("ops.spmm") is obs.NOOP_SPAN


def test_span_under_profiler_is_falsy_annotation(profile):
    def body():
        sp = obs.span("infer.fetch")
        with sp as inside:
            pass
        return sp, inside
    (sp, inside), lines = profile(body)
    assert isinstance(sp, obs.ProfilerSpan) and inside is sp
    assert not sp
    sp.set(rows=1)                      # swallowed, like NOOP_SPAN
    names = {n for ln in lines for n, _, _ in ln}
    assert "infer.fetch" in names


@pytest.mark.parametrize("executor", ["ref", "pallas"])
def test_epoch_phases_nest_under_infer_all_on_one_line(executor, profile):
    _, lines = profile(lambda: _epoch(executor))
    line = _epoch_line(lines)
    root, = [(s, e) for n, s, e in line if n == "session.infer_all"]
    got = {}
    for n, s, e in line:
        if n in PHASES:
            got.setdefault(n, []).append((s, e))
    assert set(got) == set(PHASES)
    for n, ivs in got.items():
        for s, e in ivs:
            assert root[0] <= s <= e <= root[1], n
    # one spmm and one gemm per GCN layer; an activation between layers
    assert len(got["ops.spmm"]) == N_LAYERS
    assert len(got["ops.gemm"]) == N_LAYERS
    assert len(got["ops.activation"]) == N_LAYERS - 1
    assert len(got["infer.mean_w"]) == N_LAYERS
    # the op spans and the upload sit inside the forward pass
    fwd, = got["infer.forward"]
    for n in ("model.prepare", "ops.gemm", "ops.spmm"):
        assert all(fwd[0] <= s and e <= fwd[1] for s, e in got[n]), n


def test_enabled_telemetry_under_profiler_annotates_and_records(profile):
    with Session.build(_cfg(telemetry=True)) as s:
        _, lines = profile(s.infer_all)
        recorded = {e[0]: e[4] for e in s.telemetry.tracer.events}
    names = {n for n, _, _ in _epoch_line(lines)}
    assert set(PHASES) <= names and set(PHASES) <= set(recorded)
    assert recorded["session.infer_all"]["epoch"] == 1
    assert recorded["session.infer_all"]["model"] == "gcn"


@pytest.mark.parametrize("telemetry", [False, True])
def test_run_layer_never_syncs_under_profiler(telemetry, profile,
                                              count_syncs):
    profile(lambda: _epoch(telemetry=telemetry))
    assert count_syncs == []


def test_run_layer_syncs_for_enabled_telemetry_without_profiler(
        count_syncs):
    _epoch(telemetry=True)
    assert len(count_syncs) == 2 * N_LAYERS     # every op of every layer


@pytest.mark.parametrize("executor", ["ref", "pallas"])
def test_profiler_leaves_outputs_bitwise_unchanged(executor, profile):
    H_off = _epoch(executor)
    H_on, _ = profile(lambda: _epoch(executor))
    assert H_on.dtype == H_off.dtype
    assert np.array_equal(H_on, H_off)
