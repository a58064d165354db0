"""Service-round traffic: the planning service re-plans its fleet round
after round through ``run_service``. Every round starts from the plans
the configuration states as deployed and re-plans them, warm-started,
under the round's drift epoch.

The deployed plans, the drift trace (here the load-surge schedule), the
request rates, R and the evaluation arrivals come from the cell's files.
The run's seed steers only the solver's keys and the service's
Monte-Carlo arrival draws around those rates, so every seed solves the
same rounds, and no round's problem depends on an earlier round's
random solve."""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import numpy as np

from . import reference as ref
from .deploy import Deployment
from .session import Session, Step, derive

#: run_service draws round k's solver arrivals for problem i from seed
#: ``seed + 1000 * k + 31 * i``; each call here serves round k = 1
_ROUND_OFFSET, _PROBLEM_STRIDE = 1000, 31


@dataclasses.dataclass
class State:
    core: object
    dep: Deployment
    dags: list
    scfg: object
    traffic: dict
    events: List[ref.DriftEvent]       # reference view of the drift trace
    program_events: list               # the program's DriftEvents
    program_env: object
    deployed: list                     # plans every round starts from
    session: Session
    band: float


@dataclasses.dataclass
class Round:
    j: int                  # window round, from 1
    event: int              # drift epoch served
    seed: int               # the seed handed to run_service
    plans: list
    log: object             # ServiceRoundLog


@dataclasses.dataclass
class Measured:
    rounds: List[Round]
    steps: List[Step]
    window: tuple


def _event_of(state: State, j: int) -> int:
    """Window round j serves epoch j, cycling over the trace's epochs."""
    return (j - 1) % (len(state.events) - 1) + 1


def prepare(cell, session: Session, core, mesh) -> State:
    t = cell.traffic
    with session.phase("problems"):
        dep = Deployment(cell.config)
        dags = dep.program_dags(core)
        tc = core.TrafficConfig(
            kind=t["arrivals"], rate=float(t["rate"]),
            horizon=float(t["horizon_s"]),
            max_requests=int(t["max_requests"]),
            mc_solver=int(t["mc_solver"]), miss_budget=float(t["miss_budget"]))
        pso = dataclasses.replace(dep.solver_config(core),
                                  miss_budget=tc.miss_budget)
        c = cell.config
        scfg = core.ServiceConfig(
            replan=core.ReplanConfig(
                pso=pso, migration_weight=float(c["migration_weight"]),
                traffic=tc, mesh=mesh),
            triage_margin=float(c["triage_margin"]))
        events = ref.drift_events(t["drift"], dep.env.tier,
                                  int(t["trace_rounds"]),
                                  int(t["trace_seed"]),
                                  float(t["severity"]))
        program_events = [core.DriftEvent(
            t=60.0 * k, label=e.label, bw_scale=e.bw_scale,
            power_scale=e.power_scale, price_scale=e.price_scale,
            down=e.down, load_scale=e.load_scale)
            for k, e in enumerate(events)]
        env = dep.program_env(core)
        deployed = [np.asarray(p, np.int32) for p in c["deployed_plans"]]
        if len(deployed) != len(dags) or not all(
                ref.plan_valid(dep.ref_problem(i), p)
                for i, p in enumerate(deployed)):
            raise ValueError(f"config {c['name']!r}: deployed_plans must be "
                             f"one valid plan per problem")
    state = State(core=core, dep=dep, dags=dags, scfg=scfg, traffic=t,
                  events=events, program_events=program_events,
                  program_env=env, deployed=deployed, session=session,
                  band=float(t["deadline_band"]))
    with session.phase("warm-up round"):
        _round(state, 1, seed=1)
    return state


def _round(state: State, e: int, seed: int):
    core = state.core
    trace = core.EnvTrace(base=state.program_env,
                          events=(state.program_events[0],
                                  state.program_events[e]))
    initial = [core.PSOGAResult(best_x=p, best_fitness=float("nan"),
                                best_cost=float("nan"), feasible=True,
                                iterations=0) for p in state.deployed]
    with state.session.span("service_round", event=e):
        return core.run_service(state.dags, trace, state.scfg, seed=seed,
                                initial=initial,
                                telemetry=state.session.tel)


def measure(state: State, seed: int, seconds: float) -> Measured:
    session = state.session
    fixed = int(state.traffic["fixed_rounds"])
    t0 = session.open_window()
    rounds, steps = [], []
    t1 = t0
    closed = False
    while t1 - t0 < seconds or len(rounds) < fixed:
        in_window = t1 - t0 < seconds
        if not in_window and not closed:
            session.close_window(t1)
            closed = True
        j = len(rounds) + 1
        e = _event_of(state, j)
        s = derive(seed, j, bits=30)
        rep = _round(state, e, s)
        t1 = time.perf_counter()
        log = rep.rounds[0]
        if log.rejected_apps:
            raise RuntimeError(
                f"triage rejected {log.rejected_apps} apps in round {j}; "
                f"the reference replays every app's requests")
        rounds.append(Round(j=j, event=e, seed=s, plans=list(rep.plans),
                            log=log))
        if in_window:
            it = log.replan.iterations if log.replan is not None else ()
            steps.append(Step(t0=session.host_spans[-1].t0, t1=t1,
                              iterations=tuple(int(v) for v in it),
                              draws=()))
    if not closed:
        session.close_window(t1)
    return Measured(rounds=rounds, steps=steps, window=session.window)


def _solver_draws(state: State, rnd: Round, i: int) -> np.ndarray:
    """The Monte-Carlo arrivals run_service solved problem i against."""
    t = state.traffic
    scale = state.events[rnd.event].load_scale
    return ref.sample_arrivals(
        t["arrivals"], state.dep.ref_problem(i).num_apps,
        rate=float(t["rate"]) * scale, horizon=float(t["horizon_s"]),
        max_requests=int(t["max_requests"]), n_seeds=int(t["mc_solver"]),
        seed=rnd.seed + _ROUND_OFFSET + _PROBLEM_STRIDE * i)


def _draw_counts(state: State, rnd: Round) -> tuple:
    return tuple(tuple(int(np.isfinite(d).sum())
                       for d in _solver_draws(state, rnd, i))
                 for i in range(len(state.dags)))


def _eval_draws(state: State, rnd: Round, i: int) -> np.ndarray:
    """The requests round j's served plan is judged on: ``eval_draws``
    draws fixed by the traffic file, the same for every seed."""
    t = state.traffic
    scale = state.events[rnd.event].load_scale
    return ref.sample_arrivals(
        t["arrivals"], state.dep.ref_problem(i).num_apps,
        rate=float(t["rate"]) * scale, horizon=float(t["horizon_s"]),
        max_requests=int(t["max_requests"]), n_seeds=int(t["eval_draws"]),
        seed=int(t["eval_seed"]) + 7919 * rnd.j + _PROBLEM_STRIDE * i)


def judge(state: State, m: Measured, rnd=float) -> Dict:
    """Check every served plan of every round against the reference.

    A plan from a solver rung reports the traffic key's verdict (cost
    and feasibility under the round's solver draws); the reference
    replays the plan over the same draws. With ``rnd`` a lower
    precision, the reference's own verdict stands in for the program's:
    that is the control."""
    t = state.traffic
    faithful = bool(state.scfg.replan.pso.faithful_sim)
    budget = float(t["miss_budget"])
    fixed = int(t["fixed_rounds"])
    cost_err = 0.0
    flips = bad = missing = failed = counted = 0
    costs, misses, requests = [], 0, 0
    for rnd_ in m.rounds:
        env = ref.env_at(state.dep.env, state.events[rnd_.event])
        log = rnd_.log
        if len(rnd_.plans) != len(state.dags):
            missing += abs(len(state.dags) - len(rnd_.plans))
        for i, plan in enumerate(rnd_.plans):
            prob = state.dep.ref_problem(i, env)
            valid = plan is not None and ref.plan_valid(
                prob, np.asarray(plan))
            if rnd_.j <= fixed:
                counted += 1
                evs = _eval_draws(state, rnd_, i)
                n_req = int(np.isfinite(evs).sum())
                requests += n_req
                if valid:
                    misses += sum(ref.traffic_replay(
                        prob, plan, ev, faithful).misses(prob) for ev in evs)
                    costs.append(ref.replay(prob, plan, faithful).cost)
                else:
                    misses += n_req
            if not valid:
                failed += 1
                bad += plan is not None
                continue
            if log.rung[i] not in ("warm", "burst"):
                continue
            draws = _solver_draws(state, rnd_, i)
            want = ref.traffic_verdict(prob, plan, draws, budget,
                                       state.band, faithful)
            if rnd is float:
                got_feasible = bool(log.replan.feasible[i])
                got_cost = float(log.replan.cost[i])
            else:
                low = ref.traffic_verdict(prob, plan, draws, budget, 0.0,
                                          faithful, rnd)
                got_feasible, got_cost = low.feasible_strict, low.cost
            if got_feasible not in (want.feasible_strict,
                                    want.feasible_loose):
                flips += 1
                failed += 1
                continue
            if got_feasible:
                cost_err = max(cost_err,
                               ref.relative_gap(got_cost, want.cost))
    attempted = sum(len(r.plans) for r in m.rounds) + missing
    return {
        "numbers": {"cost_err": cost_err, "verdict_flips": flips,
                    "bad_plans": bad, "missing_answers": missing},
        "quality": {
            "plan_cost": float(np.mean(costs)) if costs else None,
            "deadline_miss_share": 100.0 * misses / max(requests, 1),
            "counted": counted},
        "attempted": attempted,
        "failed": failed + missing,
    }


def end_to_end(state: State, m: Measured) -> Dict[str, float]:
    walls = [s.t1 - s.t0 for s in m.steps]
    return {"round_p90_s": float(np.percentile(walls, 90))}


def run_record(state: State, m: Measured) -> dict:
    steps = [s._replace(draws=_draw_counts(state, r))
             for s, r in zip(m.steps, m.rounds)]
    return dict(kind="service", layers=state.dep.layer_counts(),
                edges=state.dep.edge_counts(),
                servers=int(state.dep.env.power.shape[0]), apps=1,
                pop_size=int(state.scfg.replan.pso.pop_size),
                max_requests=int(state.traffic["max_requests"]),
                steps=steps)
