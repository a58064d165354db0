"""Closed-loop fleet traffic: the same fleet of problems is planned call
after call through ``run_pso_ga_batch``, each call with fresh solver keys
drawn from the run's seed. Nothing else depends on the seed."""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List

import numpy as np

from . import reference as ref
from .deploy import Deployment
from .session import Session, Step, derive

#: key of an infeasible plan: this offset plus log1p of the apps'
#: summed completion times (the planner's documented Case-3 key)
INFEASIBLE_OFFSET = 1e4


@dataclasses.dataclass
class State:
    core: object
    dep: Deployment
    problems: list          # (LayerDAG, Environment) per problem
    cfg: object
    mesh: object
    session: Session
    fixed_problems: int
    band: float


@dataclasses.dataclass
class Measured:
    calls: List[list]       # PSOGAResult lists, in call order
    steps: List[Step]       # the calls inside the window
    window: tuple


def prepare(cell, session: Session, core, mesh) -> State:
    with session.phase("problems"):
        dep = Deployment(cell.config)
        env = dep.program_env(core)
        problems = [(d, env) for d in dep.program_dags(core)]
        cfg = dep.solver_config(core)
    t = cell.traffic
    fixed = int(t["fixed_problems"])
    if fixed % len(problems):
        raise ValueError("fixed_problems must be whole calls of the fleet")
    state = State(core=core, dep=dep, problems=problems, cfg=cfg, mesh=mesh,
                  session=session, fixed_problems=fixed,
                  band=float(t["deadline_band"]))
    with session.phase("warm-up call"):
        _call(state, seed=0, c=-1)
    return state


def _call(state: State, seed: int, c: int) -> list:
    n = len(state.problems)
    seeds = [derive(seed, c, i) for i in range(n)]
    with state.session.span("fleet_call", call=c):
        return state.core.run_pso_ga_batch(
            state.problems, state.cfg, seed=seeds, mesh=state.mesh,
            telemetry=state.session.tel)


def measure(state: State, seed: int, seconds: float) -> Measured:
    session = state.session
    t0 = session.open_window()
    calls, steps = [], []
    t1 = t0
    while t1 - t0 < seconds:
        res = _call(state, seed, len(calls))
        t1 = time.perf_counter()
        calls.append(res)
        steps.append(Step(t0=session.host_spans[-1].t0, t1=t1,
                          iterations=tuple(r.iterations for r in res),
                          draws=()))
    session.close_window(t1)
    n = len(state.problems)
    while len(calls) * n < state.fixed_problems:
        calls.append(_call(state, seed, len(calls)))
    return Measured(calls=calls, steps=steps, window=(t0, t1))


def judge(state: State, m: Measured, rnd=float) -> Dict:
    """Replay every plan of every call on the reference. With ``rnd`` a
    lower precision, the reference's own numbers stand in for the
    program's: that is the control."""
    dep, faithful = state.dep, bool(state.cfg.faithful_sim)
    probs = [dep.ref_problem(i) for i in range(len(dep))]
    cost_err = key_err = 0.0
    flips = bad = missing = failed = counted = 0
    costs = []
    for c, results in enumerate(m.calls):
        if len(results) != len(probs):
            missing += abs(len(probs) - len(results))
        for i, (prob, r) in enumerate(zip(probs, results)):
            x = np.asarray(r.best_x)
            in_count = c * len(probs) + i < state.fixed_problems
            counted += in_count
            if not ref.plan_valid(prob, x):
                bad += 1
                failed += 1
                continue
            want = ref.replay(prob, x, faithful)
            if in_count:
                costs.append(want.cost)
            if rnd is float:
                got_feasible, got_cost = bool(r.feasible), float(r.best_cost)
                got_key = float(r.best_fitness)
            else:
                low = ref.replay(prob, x, faithful, rnd)
                got_feasible = low.feasible(prob)
                got_cost = low.cost if got_feasible else math.inf
                got_key = got_cost if got_feasible else (
                    INFEASIBLE_OFFSET + math.log1p(low.app_completion.sum()))
            strict = want.feasible(prob, 1.0 - state.band)
            loose = want.feasible(prob, 1.0 + state.band)
            if got_feasible not in (strict, loose):
                flips += 1
                failed += 1
                continue
            if not got_feasible:
                failed += 1
                key_err = max(key_err, ref.relative_gap(
                    got_key, INFEASIBLE_OFFSET
                    + math.log1p(want.app_completion.sum())))
                continue
            cost_err = max(cost_err, ref.relative_gap(got_cost, want.cost))
            key_err = max(key_err, ref.relative_gap(got_key, want.cost))
    attempted = sum(len(r) for r in m.calls) + missing
    return {
        "numbers": {"cost_err": cost_err, "key_err": key_err,
                    "verdict_flips": flips, "bad_plans": bad,
                    "missing_answers": missing},
        "quality": {"plan_cost": float(np.mean(costs)) if costs else None,
                    "counted": counted},
        "attempted": attempted,
        "failed": failed + missing,
    }


def end_to_end(state: State, m: Measured) -> Dict[str, float]:
    plans = len(m.steps) * len(state.problems)
    return {"plans_per_s": plans / (m.window[1] - m.window[0])}


def run_record(state: State, m: Measured) -> dict:
    return dict(kind="fleet", layers=state.dep.layer_counts(),
                edges=state.dep.edge_counts(),
                servers=int(state.dep.env.power.shape[0]), apps=1,
                pop_size=int(state.cfg.pop_size), max_requests=0,
                steps=m.steps)
