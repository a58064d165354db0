"""What a window solves does not depend on ``--seed``; quality is judged
over the cell's fixed count of problems or rounds.

The planner's entry points are replaced by recorders that answer at once
with each problem's all-on-its-device plan, so these tests see exactly
what the harness hands the planner."""
import json
import types

import numpy as np
import pytest

import repro.core as core
from harness import fleet, service
from harness.session import Session, derive
from harness.spec import ROOT, load_cell

WORKLOADS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]
LOOPS = {"fleet_closed_loop": fleet, "service_rounds": service}


def _home_plan(dag):
    return np.full(dag.num_layers, int(dag.pinned[dag.pinned >= 0][0]),
                   np.int32)


class Recorder:
    """``repro.core`` with its two entry points recorded and stubbed."""

    def __init__(self):
        self.calls = []

    def namespace(self):
        ns = types.SimpleNamespace(**{k: getattr(core, k)
                                      for k in core.__all__})
        ns.run_pso_ga_batch = self.run_pso_ga_batch
        ns.run_service = self.run_service
        return ns

    def run_pso_ga_batch(self, problems, cfg, seed, mesh=None,
                         telemetry=None):
        self.calls.append({"problems": problems, "cfg": cfg,
                           "seeds": list(seed)})
        return [core.PSOGAResult(best_x=_home_plan(d), best_fitness=1.0,
                                 best_cost=1.0, feasible=True, iterations=3)
                for d, _ in problems]

    def run_service(self, dags, trace, cfg, seed, initial=None,
                    telemetry=None):
        self.calls.append({"dags": dags, "trace": trace, "cfg": cfg,
                           "seed": seed, "initial": initial})
        n = len(dags)
        plans = [_home_plan(d) for d in dags]
        replan = types.SimpleNamespace(iterations=np.full(n, 3),
                                       feasible=np.ones(n, bool),
                                       cost=np.ones(n))
        log = types.SimpleNamespace(rejected_apps=0, rung=("warm",) * n,
                                    replan=replan)
        return types.SimpleNamespace(
            cold=[core.PSOGAResult(best_x=p, best_fitness=1.0,
                                   best_cost=1.0, feasible=True,
                                   iterations=3) for p in plans],
            rounds=[log], plans=plans)


def _drive(workload, seed, seconds=0.0):
    cell = load_cell(workload)
    rec = Recorder()
    session = Session(0.0, trace=False)
    loop = LOOPS[cell.traffic["kind"]]
    state = loop.prepare(cell, session, rec.namespace(), mesh=None)
    rec.calls.clear()                       # set-up is not the window
    measured = loop.measure(state, seed, seconds)
    return cell, loop, state, measured, rec


def _work(call):
    """What one planner call was asked to solve, without its seeds."""
    if "problems" in call:
        return [(d.compute.tolist(), d.edges.tolist(), d.edge_mb.tolist(),
                 d.deadline.tolist(), d.pinned.tolist(),
                 e.bandwidth.tolist(), e.power.tolist(),
                 e.cost_per_sec.tolist()) for d, e in call["problems"]]
    tr = call["trace"]
    return ([(d.compute.tolist(), d.deadline.tolist(), d.pinned.tolist())
             for d in call["dags"]],
            [(ev.load_scale, ev.bw_scale.tolist(), ev.price_scale.tolist(),
              ev.down.tolist()) for ev in tr.events],
            repr(call["cfg"]))


@pytest.mark.parametrize("workload", WORKLOADS)
def test_seed_changes_only_the_solver_keys(workload):
    a = _drive(workload, 7)[-1].calls
    b = _drive(workload, 2 ** 31 + 11)[-1].calls
    assert len(a) == len(b) > 0
    for ca, cb in zip(a, b):
        assert _work(ca) == _work(cb)
        if "seeds" in ca:
            assert ca["seeds"] != cb["seeds"]
            assert len(set(ca["seeds"])) == len(ca["seeds"])
        else:
            assert ca["seed"] != cb["seed"]


def test_service_rates_follow_the_fixed_surge():
    cell, _, state, _, rec = _drive("service.bursty10", 5)
    scales = [c["trace"].events[1].load_scale for c in rec.calls]
    assert scales == [e.load_scale for e in
                      state.events[1:len(scales) + 1]]
    assert len(set(scales)) > 1
    tc = rec.calls[0]["cfg"].replan.traffic
    assert tc.rate == cell.traffic["rate"]
    assert tc.max_requests == cell.traffic["max_requests"]


@pytest.mark.parametrize("workload", WORKLOADS)
@pytest.mark.parametrize("seconds", [0.0, 0.05])
def test_quality_covers_the_fixed_count(workload, seconds):
    cell, loop, state, measured, _ = _drive(workload, 3, seconds)
    verdict = loop.judge(state, measured)
    t = cell.traffic
    fixed = t.get("fixed_problems") or t["fixed_rounds"] * len(state.dags)
    assert verdict["quality"]["counted"] == fixed
    assert verdict["attempted"] >= fixed


def test_derive_is_a_31_bit_function_of_seed_and_part():
    assert derive(2 ** 31 + 5, 1) == derive(2 ** 31 + 5, 1)
    assert derive(2 ** 31 + 5, 1) != derive(2 ** 31 + 5, 2)
    assert 0 <= derive(2 ** 40, 3) < 2 ** 31
    assert 0 <= derive(9, 3, bits=30) < 2 ** 30
