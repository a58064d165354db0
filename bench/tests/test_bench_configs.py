"""Each configuration is a deployment of its own, and each cell runs its
configuration on the chips it asks for: a configuration with another's
source and cuts is no new deployment, and a mesh that shards only where
a cell has the chips to shard over."""
import json

import pytest

from harness.spec import ROOT, load_cell

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = SPEC["configs"]
WORKLOADS = [w["name"] for w in SPEC["workloads"]]


def _identity(entry):
    return entry["source"], tuple(sorted(entry["reduced"]))


@pytest.mark.parametrize("entry", CONFIGS, ids=lambda c: c["name"])
def test_each_configuration_is_new(entry):
    same = [c["name"] for c in CONFIGS
            if c["name"] != entry["name"] and _identity(c) == _identity(entry)]
    assert not same, f"{entry['name']} has the source and cuts of {same}"


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_mesh_fits_its_chips(workload):
    cell = load_cell(workload)
    sharded = cell.config.get("mesh", "none") != "none"
    assert sharded == (cell.chips > 1), (cell.chips, cell.config["mesh"])
