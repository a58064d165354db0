"""The benchmark's plain references agree with the planner's own
semantics on seeded problems: the schedule replay and Eq. 8 cost, the
queue-aware traffic replay, and the request and drift generators."""
import numpy as np
import pytest

import jax.numpy as jnp

from harness import reference as ref
from harness.deploy import Deployment
from harness.spec import load_cell
from repro.core import (SimProblem, TRACE_KINDS, TRAFFIC_KINDS,
                        paper_environment, sample_arrivals, sample_trace,
                        simulate_np, simulate_traffic_swarm)
from repro.core.simulator import pad_problem


@pytest.fixture(scope="module")
def service_dep():
    return Deployment(load_cell("service.bursty10").config)


@pytest.fixture(scope="module")
def fleet_dep():
    return Deployment(load_cell("fleet.mixed128").config)


def _program_problem(dep, i, core_env=None):
    import repro.core as core
    dag = dep.program_dags(core)[i]
    return SimProblem.build(dag, core_env or dep.program_env(core))


def _random_plan(rng, prob: ref.Problem) -> np.ndarray:
    """A plan on live links: each layer on its parent's server or on a
    random server linked to it."""
    x = np.zeros(prob.num_layers, np.int64)
    for j in prob.order:
        if prob.pinned[j] >= 0:
            x[j] = prob.pinned[j]
            continue
        src = x[prob.parents[j][0][0]] if prob.parents[j] else 0
        ok = [s for s in range(prob.num_servers)
              if s == src or prob.link_ok[src, s]]
        x[j] = rng.choice(ok)
    return x


def test_environment_is_the_papers():
    env = Deployment(load_cell("fleet.mixed128").config).env
    want = paper_environment()
    np.testing.assert_array_equal(env.power, want.power)
    np.testing.assert_allclose(env.cost_per_sec, want.cost_per_sec)
    np.testing.assert_array_equal(env.tier, want.tier)
    np.testing.assert_array_equal(env.bandwidth, want.bandwidth)
    np.testing.assert_allclose(env.tran_cost, want.tran_cost)


@pytest.mark.parametrize("faithful", [True, False])
@pytest.mark.parametrize("i", [0, 1, 8, 127])
def test_replay_matches_simulate_np(fleet_dep, i, faithful):
    rng = np.random.default_rng(i)
    prob = fleet_dep.ref_problem(i)
    sp = _program_problem(fleet_dep, i)
    for _ in range(5):
        x = _random_plan(rng, prob)
        got = ref.replay(prob, x, faithful)
        want = simulate_np(sp, x, faithful)
        np.testing.assert_allclose(got.cost, float(want.total_cost),
                                   rtol=1e-12)
        np.testing.assert_allclose(got.app_completion, want.app_completion,
                                   rtol=1e-12)
        assert got.feasible(prob) == bool(want.feasible)


def test_replay_flags_broken_links(fleet_dep):
    prob = fleet_dep.ref_problem(0)
    x = np.full(prob.num_layers, 15)
    x[0] = prob.pinned[0]
    for s in range(prob.num_servers):
        if not prob.link_ok[x[0], s]:
            x[1] = s
            break
    assert not ref.plan_valid(prob, x)
    assert not ref.replay(prob, x).static_ok
    assert not bool(simulate_np(_program_problem(fleet_dep, 0), x).feasible)


@pytest.mark.parametrize("faithful", [True, False])
@pytest.mark.parametrize("i", [0, 2])
def test_traffic_replay_matches_scan(service_dep, i, faithful):
    rng = np.random.default_rng(10 + i)
    prob = service_dep.ref_problem(i)
    pp = pad_problem(_program_problem(service_dep, i))
    arr = sample_arrivals("bursty", 1, rate=2.0, horizon=30.0,
                          max_requests=8, n_seeds=1, seed=i).t[0]
    for _ in range(3):
        x = _random_plan(rng, prob)
        got = ref.traffic_replay(prob, x, arr, faithful)
        sim = simulate_traffic_swarm(pp, jnp.asarray(x, jnp.int32)[None],
                                     jnp.asarray(arr), faithful)
        lat = np.asarray(sim.latency[0])
        mask = ~np.isnan(got.latency)
        np.testing.assert_allclose(got.latency[mask], lat[mask],
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(got.cost, float(sim.total_cost[0]),
                                   rtol=1e-4)
        assert got.static_ok == bool(sim.static_ok[0])


@pytest.mark.parametrize("kind", TRAFFIC_KINDS)
def test_arrivals_match_the_program(kind):
    seed = 2 ** 31 + 77
    got = ref.sample_arrivals(kind, 3, rate=0.9, horizon=30.0,
                              max_requests=8, n_seeds=4, seed=seed)
    want = sample_arrivals(kind, 3, rate=0.9, horizon=30.0, max_requests=8,
                           n_seeds=4, seed=seed).t
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("kind", TRACE_KINDS)
def test_drift_matches_the_program(kind):
    env = paper_environment()
    got = ref.drift_events(kind, env.tier, 6, 1907, 0.6)
    want = sample_trace(kind, env, rounds=6, seed=1907, severity=0.6)
    base = ref.environment_from_config(
        load_cell("service.bursty10").config["environment"])
    for k, (g, w) in enumerate(zip(got, want.events)):
        assert g.label == w.label
        assert g.load_scale == w.load_scale
        e_got, e_want = ref.env_at(base, g), want.env_at(k)
        np.testing.assert_array_equal(e_got.bandwidth, e_want.bandwidth)
        np.testing.assert_allclose(e_got.cost_per_sec, e_want.cost_per_sec)
        np.testing.assert_array_equal(e_got.power, e_want.power)


def test_bfloat16_rounding_moves_the_cost(fleet_dep):
    """The control's precision changes what the reference computes."""
    prob = fleet_dep.ref_problem(8)
    x = _random_plan(np.random.default_rng(3), prob)
    hi = ref.replay(prob, x).cost
    lo = ref.replay(prob, x, rnd=ref.to_bfloat16).cost
    assert ref.relative_gap(lo, hi) > 1e-4
