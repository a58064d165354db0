"""Every cell, configuration, traffic mix and metric is found by name,
and new ones are picked up as new files, without editing one that
exists. The entry point refuses to run without a TPU or without the
planner."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from harness import reference as ref
from harness.deploy import Deployment
from harness.spec import (BENCH_DIR, ROOT, SpecError, load_cell,
                          load_reader)

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_cell_resolves(workload):
    cell = load_cell(workload)
    assert cell.end_to_end and cell.per_layer
    names = {m.name for m in cell.end_to_end}
    assert {"setup_s", "plan_cost"} <= names
    for m in cell.per_layer:
        assert m.moves in names, (m.name, m.moves)
        assert callable(load_reader(m.name))
    dep = Deployment(cell.config)
    assert len(dep) == cell.config["n_problems"]


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files_state_their_cuts(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    for key in entry["reduced"]:
        assert cfg[key] != cfg["published"][key]
        assert key in cfg["assumed"]


def test_unknown_workload_is_refused():
    with pytest.raises(SpecError):
        load_cell("no.such.cell")


def _copy_bench(tmp_path):
    shutil.copytree(BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path / "bench"


def test_new_files_are_found_by_name(tmp_path):
    """A traffic mix, a configuration and a metric added as files, with
    entries in BENCHMARK.json, make a new cell; no file is edited."""
    bench = _copy_bench(tmp_path)
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    traffic = json.loads((bench / "traffic" / "bursty_surge.json")
                         .read_text())
    traffic.update(max_requests=64, fixed_rounds=4)
    (bench / "traffic" / "bursty_surge_r64.json").write_text(
        json.dumps(traffic))
    cfg = json.loads((bench / "configs" / "paper-service.json").read_text())
    cfg.update(name="paper-service-3", n_problems=3,
               problems=cfg["problems"][:3],
               deployed_plans=cfg["deployed_plans"][:3])
    (bench / "configs" / "paper-service-3.json").write_text(json.dumps(cfg))
    (bench / "metrics" / "service.rounds.py").write_text(
        "def read(run):\n    return float(len(run.steps))\n")
    spec["configs"].append({"name": "paper-service-3",
                            "source": "https://arxiv.org/abs/1907.13306",
                            "file": "bench/configs/paper-service-3.json",
                            "reduced": ["max_iters"], "why": "test"})
    spec["workloads"].append({"name": "service.r64", "config":
                              "paper-service-3", "traffic":
                              "bursty_surge_r64", "chips": 1, "why": "t"})
    spec["per_layer"].append({"name": "service.rounds", "unit": "rounds",
                              "better": "higher", "source": "host_clock",
                              "layer": "service loop",
                              "moves": "round_p90_s",
                              "workloads": ["service.r64"]})
    for m in spec["end_to_end"]:
        if m.get("workloads") == ["service.bursty10"]:
            m["workloads"].append("service.r64")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = load_cell("service.r64", root=tmp_path, bench_dir=bench)
    assert cell.traffic["max_requests"] == 64
    assert len(Deployment(cell.config)) == 3
    assert "service.rounds" in [m.name for m in cell.per_layer]
    read = load_reader("service.rounds", bench_dir=bench)
    assert read(type("R", (), {"steps": [1, 2]})()) == 2.0
    # the cells that were there see none of it
    old = load_cell("service.bursty10", root=tmp_path, bench_dir=bench)
    assert "service.rounds" not in [m.name for m in old.per_layer]


def _run(args, cwd, env=None):
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          capture_output=True, text=True, timeout=300,
                          env=env)


def test_no_tpu_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = _run(["--workload", "fleet.mixed128", "--seed", "1",
                "--seconds", "1", "--trace", "0"], ROOT, env)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "no TPU" in out.stderr


def test_benchmark_alone_means_no_result(tmp_path):
    _copy_bench(tmp_path)
    out = _run(["--workload", "fleet.mixed128", "--seed", "1",
                "--seconds", "1", "--trace", "0"], tmp_path)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


def test_unknown_device_kind_has_no_peaks():
    from harness import peaks
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")
    w = peaks.schedule_replay(11, 10, 20, 1, 100)
    assert w.bound("TPU v5 lite") == "memory"
    assert w.seconds("TPU v5 lite") > 0.0


def test_reference_rejects_cycles():
    env = ref.environment_from_config(
        load_cell("fleet.mixed128").config["environment"])
    with pytest.raises(ValueError):
        ref.Problem([1.0, 1.0], [[0, 1], [1, 0]], [1.0, 1.0], [0, 0], [1.0],
                    [0, -1], env)
