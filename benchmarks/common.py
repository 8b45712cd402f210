"""Benchmark utilities: timing, CSV rows, subprocess meshes."""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys
import time
from typing import Callable, List

import jax

ROOT = pathlib.Path(__file__).resolve().parents[1]
ROWS: List[str] = []


def emit(name: str, us_per_call: float, derived: str = ""):
    row = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def time_fn(fn: Callable, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall time (s) of fn(*args) with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def time_host(fn: Callable, *args, iters: int = 3):
    """Median wall time for host (numpy) functions; returns (t, result)."""
    ts, out = [], None
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2], out


def run_dist_script(script: str, smoke: bool = False, n_devices: int = 8,
                    timeout: int = 3000) -> None:
    """Fill the @SMOKE@ token, run the script under a forced host-device
    count, and emit its ``CSV,name,us,derived`` rows — the shared
    protocol of every subprocess-mesh bench."""
    out = run_devices_subprocess(script.replace("@SMOKE@", str(int(smoke))),
                                 n_devices=n_devices, timeout=timeout)
    for line in out.splitlines():
        if line.startswith("CSV,"):
            _, name, us, derived = line.split(",", 3)
            emit(name, float(us), derived)


def run_devices_subprocess(script: str, n_devices: int = 8,
                           timeout: int = 1800) -> str:
    """Run a python snippet under a forced host-device count; returns
    stdout.  Keeps the parent process single-device.  Refuses where JAX
    found a chip: the forced devices would run the mesh on the CPU."""
    from repro.runtime import require_host_platform
    require_host_platform("benchmarks.common.run_devices_subprocess")
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = str(ROOT / "src")
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout,
                         cwd=str(ROOT))
    if res.returncode != 0:
        raise RuntimeError(res.stdout + "\n" + res.stderr[-3000:])
    return res.stdout
