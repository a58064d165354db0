"""Readings that the limits of a cell's comparison are set from.

    python3 bench/control.py --workload fleet.mixed128 \\
        --seeds 101 102 103 --out readings.jsonl

Sets the cell up once, then for each seed drives the planner through the
cell's fixed count of problems or rounds (no timed window) and compares
its answers with the reference twice: as the program gave them, and with
the reference computed in bfloat16 put in the program's place (the
control). One JSON line per seed. The benchmark's own runs never run
this; it needs the cell's chips, like ``run.py``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as bench  # noqa: E402
from harness.reference import to_bfloat16  # noqa: E402
from harness.session import Session  # noqa: E402
from harness.spec import SpecError, load_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
    except SpecError as e:
        return bench._fail(str(e))
    devices = bench.chip_devices(cell)
    if devices is None:
        return 2
    import repro.core as core
    mesh = bench.cell_mesh(cell)
    session = Session(T_START, trace=False)
    loop = bench.LOOPS[cell.traffic["kind"]]
    state = loop.prepare(cell, session, core, mesh)
    limits = cell.traffic["limits"]
    with open(args.out, "a") as f:
        for seed in args.seeds:
            t0 = time.perf_counter()
            m = loop.measure(state, seed, 0.0)
            program = loop.judge(state, m)
            control = loop.judge(state, m, rnd=to_bfloat16)
            row = {
                "workload": args.workload, "seed": seed,
                "attempted": program["attempted"],
                "failed": program["failed"],
                "quality": program["quality"],
                "program": program["numbers"],
                "control": control["numbers"],
                "program_correct": all(v <= limits[k] for k, v in
                                       program["numbers"].items()),
                "control_correct": all(v <= limits[k] for k, v in
                                       control["numbers"].items()),
                "seconds": time.perf_counter() - t0}
            f.write(json.dumps(row) + "\n")
            f.flush()
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
