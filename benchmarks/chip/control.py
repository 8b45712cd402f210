#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``: the reference
itself put in the program's place, with its matmuls one precision step
below what the configuration states (three bfloat16 passes for float32
at HIGHEST), at the cell's own size.  It must come out not correct.

    python3 benchmarks/chip/control.py --workload gat4-papers100m.epoch \
        --seeds 101 102 103

For each seed it makes a whole run of the cell (``run.run``: the
Session build, the inputs from the seed, the warm-up and a window of
one step) and hands what the window produced to the run's own
comparison (``run.check``), with the control's rows in place of the
program's.  Each number compared is printed beside its limit, with
``correct``; the program's own numbers on the same inputs are printed
too, as a sound run's readings.  The benchmark's own runs never run
it.  The last line of stdout is one JSON object with every reading.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import bench  # noqa: E402
import run as runmod  # noqa: E402


def control_run(cell: bench.Cell, seed: int, root: Path = bench.ROOT):
    """The result of one run of ``cell`` with the control in the
    program's place."""
    return runmod.run(cell, seed, 1e-6, False, root=root, control=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    out = {}
    for seed in args.seeds:
        res = control_run(cell, seed)
        out[str(seed)] = {"correct": res["correct"], "checks": res["checks"],
                          "program_checks": res["program_checks"]}
        runmod.log(f"[control] {cell.name} seed {seed}: correct "
                   f"{res['correct']}")
    print(json.dumps({"workload": cell.name, "control": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
