"""Run one cell of the planner's benchmark on the chips of this machine.

    python3 bench/run.py --workload fleet.mixed128 --seed 7 --seconds 51 \\
        --trace 0

Reads the cell from ``BENCHMARK.json`` and the data files it names,
builds the problems, warms every program the window uses, then drives
the planner through its normal entry points for ``--seconds`` seconds.
The plans the window produced are then checked against the plain
reference. The last line on standard output is one JSON object: the
cell's end-to-end metrics (``--trace 0``) or its per-layer metrics read
from a profiler trace (``--trace 1``), whether the plans were correct,
and the device. The numbers compared, each beside its limit, are the
last lines on standard error.

Exits non-zero, with no result line, when JAX finds no TPU or fewer
chips than the cell asks for, or when the program is missing.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))

from harness import fleet, service, tracing  # noqa: E402
from harness.session import Run, Session, memory_peak_bytes  # noqa: E402
from harness.spec import SpecError, load_cell, load_reader  # noqa: E402

#: the persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
LOOPS = {"fleet_closed_loop": fleet, "service_rounds": service}


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def chip_devices(cell):
    """Put the planner on the path and JAX's compilation cache in the
    checkout; return the cell's chips, or None (with the reason on
    standard error) when this machine lacks them."""
    if not (ROOT / "src" / "repro").is_dir():
        _fail(f"the planner is not in this checkout ({ROOT / 'src'})")
        return None
    sys.path.insert(0, str(ROOT / "src"))
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"JAX found no TPU (platform {devices[0].platform!r}); "
              f"the benchmark never falls back")
        return None
    if len(devices) < cell.chips:
        _fail(f"{cell.name} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}")
        return None
    return devices[:cell.chips]


def cell_mesh(cell):
    """The solver's device mesh the configuration names, or None."""
    if cell.config.get("mesh", "none") == "none":
        return None
    from repro.launch.mesh import resolve_mesh
    return resolve_mesh(cell.config["mesh"])


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = load_cell(args.workload)
    except SpecError as e:
        return _fail(str(e))
    devices = chip_devices(cell)
    if devices is None:
        return 2
    result = execute(cell, args, devices)
    print(json.dumps(result), flush=True)
    return 0


def execute(cell, args, devices) -> dict:
    """Everything after the look for the chip: set-up, window, checks."""
    import repro.core as core
    mesh = cell_mesh(cell)
    session = Session(T_START, trace=bool(args.trace),
                      telemetry_cls=core.Telemetry)
    loop = LOOPS[cell.traffic["kind"]]
    state = loop.prepare(cell, session, core, mesh)
    measured = loop.measure(state, args.seed, args.seconds)
    print(session.summary(), file=sys.stderr, flush=True)
    memory = memory_peak_bytes(devices)
    verdict = loop.judge(state, measured)
    limits = cell.traffic["limits"]
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in verdict["numbers"].items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory}
    result = {"correct": correct, "attempted": verdict["attempted"],
              "failed": verdict["failed"]}
    if args.trace:
        run = Run(device_kind=devices[0].device_kind, window=session.window,
                  spans=session.host_spans + session.program_spans(),
                  compiles_in_window=session.window_compiles,
                  trace=session.device_trace,
                  **loop.run_record(state, measured))
        metrics = {}
        for m in cell.per_layer:
            value = load_reader(m.name)(run)
            if value is not None:
                metrics[m.name] = {"value": value, "unit": m.unit}
        window = session.window
        device["busy_s"] = tracing.busy_s(run.trace, window)
        device["window_s"] = window[1] - window[0]
        spans = [[s.name, s.t0, s.t1] for s in run.spans]
        result["breakdown"] = tracing.breakdown(run.trace, window, spans)
    else:
        values = {"setup_s": session.setup_s,
                  **loop.end_to_end(state, measured), **verdict["quality"]}
        metrics = {m.name: {"value": values[m.name], "unit": m.unit}
                   for m in cell.end_to_end}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return result


if __name__ == "__main__":
    sys.exit(main())
