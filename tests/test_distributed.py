"""Distributed primitive + engine correctness on an 8-device host mesh.

Runs tests/helpers/dist_check.py in a subprocess (the main process must
keep 1 device; XLA locks the count at first init)."""
import os
import pathlib
import subprocess
import sys
import time

import pytest

HELPER = pathlib.Path(__file__).parent / "helpers" / "dist_check.py"
TUNED = pathlib.Path(__file__).parent / "helpers" / "tuned_check.py"

# a wedged collective stops the helper's main-thread heartbeat; no
# single check (compiles included) legitimately goes this long silent
STALE_S = 300.0
TOTAL_S = 1800.0
POLL_S = 5.0


def _read_heartbeat(path: pathlib.Path):
    """(mtime, stage-label) of the helper's last main-thread beat."""
    try:
        return os.path.getmtime(path), path.read_text().split(" ", 1)[-1].strip()
    except OSError:
        return None, "<no heartbeat yet>"


def _run_once(script: pathlib.Path, hb: pathlib.Path, *args: str):
    """Run the helper (with ``args``), polling its heartbeat.  Returns
    ``(CompletedProcess | None, wedged_stage | None)`` — a wedge (stale
    heartbeat or total-budget blowout) kills the process and reports the
    stage it died in."""
    proc = subprocess.Popen([sys.executable, str(script),
                             "--heartbeat", str(hb), *args],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    start = time.monotonic()
    while True:
        try:
            out, err = proc.communicate(timeout=POLL_S)
            return subprocess.CompletedProcess(proc.args, proc.returncode,
                                               out, err), None
        except subprocess.TimeoutExpired:
            pass
        mtime, stage = _read_heartbeat(hb)
        silent = (time.time() - mtime if mtime is not None
                  else time.monotonic() - start)
        if silent > STALE_S or time.monotonic() - start > TOTAL_S:
            proc.kill()
            out, err = proc.communicate()
            print(f"# {script.name} heartbeat silent {silent:.0f}s "
                  f"(last stage: {stage}); killed")
            print(out[-2000:])
            return None, stage


def _run_check(script: pathlib.Path, tmp_path,
               *args: str) -> subprocess.CompletedProcess:
    """One retry on a WEDGE only: 8 forced host devices on a small box
    can deadlock their collectives (threads asleep, ~0 CPU) — an
    environmental hang, observed rarely and never reproducible
    standalone.  The helper heartbeats from its main thread per check,
    so a wedge is detected within ``STALE_S`` and diagnosed with the
    stage it stopped in.  A real check failure exits nonzero fast and
    is NOT retried."""
    for attempt in (0, 1):
        hb = tmp_path / f"{script.stem}.heartbeat.{attempt}"
        res, stage = _run_once(script, hb, *args)
        if res is not None:
            if attempt:
                print(f"# {script.name}: retry succeeded after a wedge")
            return res
        if attempt:
            pytest.fail(f"{script.name} wedged twice (stage: {stage})")
        print(f"# {script.name} wedged at stage {stage!r} (collective "
              "deadlock on oversubscribed fake devices); retrying once")


@pytest.mark.slow
def test_distributed_primitives_and_engines(tmp_path):
    res = _run_check(HELPER, tmp_path)
    print(res.stdout)
    print(res.stderr[-2000:] if res.returncode else "")
    assert res.returncode == 0, res.stdout + res.stderr[-2000:]
    assert "ALL DISTRIBUTED CHECKS PASSED" in res.stdout


@pytest.mark.slow
def test_tuned_variants_match_baseline(tmp_path):
    """§Perf hillclimbs (moe_ep, cp_decode) are numerics-preserving."""
    res = _run_check(TUNED, tmp_path)
    print(res.stdout)
    assert res.returncode == 0, res.stdout + res.stderr[-2000:]
    assert "ALL TUNED CHECKS PASSED" in res.stdout


GAT_CASES = ["session_vs_ref/heads4_p2_m2", "session_vs_ref/heads2_p2_m2",
             "session_vs_ref/heads2_p2_m4", "heads1_bitwise_parent",
             "unfused_scores/heads4_p2_m2", "unfused_scores/heads2_p2_m4",
             "delta_bitwise/heads4", "plan_built_once"]


@pytest.fixture(scope="module")
def gat_cases(tmp_path_factory):
    """Multi-head GAT on the mesh: every case of ``dist_check.py --gat``
    in one child process, {case: (ok, detail)}."""
    res = _run_check(HELPER, tmp_path_factory.mktemp("gat"), "--gat")
    assert res.returncode == 0, res.stdout + res.stderr[-2000:]
    assert "GAT CASES DONE" in res.stdout, res.stdout
    out = {}
    for line in res.stdout.splitlines():
        if line.startswith("CASE "):
            _, name, verdict, detail = (line.split(" ", 3) + [""])[:4]
            out[name] = (verdict == "OK", detail)
    return out


@pytest.mark.slow
@pytest.mark.parametrize("case", GAT_CASES)
def test_dist_gat(gat_cases, case):
    ok, detail = gat_cases[case]
    assert ok, f"{case}: {detail}"


def test_heads_the_mesh_cannot_split_are_a_config_error():
    """heads=3 on m=2 fails at Session.build, naming both fields."""
    from repro.api import DealConfig, Session
    from repro.api.config import ConfigError
    cfg = DealConfig.from_dict({
        "model": {"name": "gat", "d_feature": 48, "heads": 3},
        "partition": {"p": 2, "m": 2},
        "executor": {"name": "dist"}})
    with pytest.raises(ConfigError) as exc:
        Session.build(cfg)
    assert "model.heads" in str(exc.value)
    assert "partition.m" in str(exc.value)
