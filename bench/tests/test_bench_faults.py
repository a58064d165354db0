"""A sound run at a tiny size is judged correct, and each fault a cell
can have, planted in the planner's answers where they are produced, makes
``correct`` false. Runs the real planner on the CPU; only the look for a
chip is skipped."""
import dataclasses
import types

import numpy as np
import pytest

import repro.core as core
from harness import fleet, service
from harness.session import Session
from harness.spec import load_cell


def _tiny(workload, n, fixed):
    cell = load_cell(workload)
    cfg = dict(cell.config, problems=cell.config["problems"][:n],
               n_problems=n, pop_size=8, max_iters=3)
    if "deployed_plans" in cfg:
        cfg["deployed_plans"] = cfg["deployed_plans"][:n]
    traffic = dict(cell.traffic, **fixed)
    return dataclasses.replace(cell, config=cfg, traffic=traffic)


def _correct(cell, verdict):
    return all(v <= cell.traffic["limits"][k]
               for k, v in verdict["numbers"].items())


def _with(state, **entry_points):
    ns = types.SimpleNamespace(**{k: getattr(core, k) for k in core.__all__})
    for k, v in entry_points.items():
        setattr(ns, k, v)
    return dataclasses.replace(state, core=ns)


@pytest.fixture(scope="module")
def fleet_run():
    cell = _tiny("fleet.mixed128", 8, {"fixed_problems": 16})
    state = fleet.prepare(cell, Session(0.0, trace=False), core, None)
    return cell, state


@pytest.fixture(scope="module")
def service_run():
    cell = _tiny("service.bursty10", 3, {"fixed_rounds": 2})
    state = service.prepare(cell, Session(0.0, trace=False), core, None)
    return cell, state


def _fleet_fault(kind):
    def solve(problems, cfg, **kw):
        res = core.run_pso_ga_batch(problems, cfg, **kw)
        n = len(res)
        if kind == "answer_altered":
            res[0] = res[0]._replace(feasible=not res[0].feasible)
            res = [r._replace(best_cost=r.best_cost * 1.01,
                              best_fitness=r.best_fitness * 1.01)
                   for r in res]
        elif kind == "half_left_out":
            res = res[:n // 2]
        elif kind == "exchange_left_out":
            # three answers of every four never come back, as when the
            # gather of a sharded solve is left out: zeroed buffers stay
            res = [r if i % 4 == 0 else r._replace(
                best_x=np.zeros_like(r.best_x), best_cost=0.0,
                best_fitness=0.0) for i, r in enumerate(res)]
        return res
    return solve


def test_sound_fleet_run_is_correct(fleet_run):
    cell, state = fleet_run
    verdict = fleet.judge(state, fleet.measure(state, 2 ** 31 + 9, 0.0))
    assert _correct(cell, verdict), verdict["numbers"]
    assert verdict["quality"]["counted"] == 16


@pytest.mark.parametrize("kind", ["answer_altered", "half_left_out",
                                  "exchange_left_out"])
def test_fleet_fault_is_not_correct(fleet_run, kind):
    cell, state = fleet_run
    broken = _with(state, run_pso_ga_batch=_fleet_fault(kind))
    verdict = fleet.judge(broken, fleet.measure(broken, 5, 0.0))
    assert not _correct(cell, verdict), verdict["numbers"]


def _service_fault(kind):
    def serve(dags, trace, cfg, **kw):
        rep = core.run_service(dags, trace, cfg, **kw)
        if kind == "answer_altered":
            rl = rep.rounds[0].replan
            rl.feasible[0] = not rl.feasible[0]
            rl.cost[:] = np.where(np.isfinite(rl.cost), rl.cost * 1.01,
                                  rl.cost)
        elif kind == "half_left_out":
            rep.plans = rep.plans[:len(rep.plans) // 2]
        return rep
    return serve


def test_sound_service_run_is_correct(service_run):
    cell, state = service_run
    verdict = service.judge(state, service.measure(state, 2 ** 31 + 9, 0.0))
    assert _correct(cell, verdict), verdict["numbers"]
    assert verdict["quality"]["counted"] == 2 * 3


@pytest.mark.parametrize("kind", ["answer_altered", "half_left_out"])
def test_service_fault_is_not_correct(service_run, kind):
    cell, state = service_run
    broken = _with(state, run_service=_service_fault(kind))
    verdict = service.judge(broken, service.measure(broken, 5, 0.0))
    assert not _correct(cell, verdict), verdict["numbers"]


@pytest.mark.parametrize("workload", ["fleet", "service"])
def test_bfloat16_control_is_not_correct(fleet_run, service_run, workload):
    """The reference in bfloat16, put in the planner's place, fails the
    comparison that the planner's own answers pass."""
    loop, (cell, state) = ((fleet, fleet_run) if workload == "fleet"
                             else (service, service_run))
    from harness.reference import to_bfloat16
    m = loop.measure(state, 11, 0.0)
    assert _correct(cell, loop.judge(state, m))
    assert not _correct(cell, loop.judge(state, m, rnd=to_bfloat16))
