"""Fleet-scale batched PSO-GA (repro.core.batch, DESIGN.md §4):
sequential parity, padding masks, and per-problem convergence freezing."""
import numpy as np
import pytest

from repro.core import (PSOGAConfig, SimProblem, pack_problems,
                        pad_problem, paper_environment, run_pso_ga,
                        run_pso_ga_batch, sample_environment,
                        simulate_np, simulate_padded, zoo)
from repro.core.batch import bucket_size
from repro.core.dag import LayerDAG

FAST = PSOGAConfig(pop_size=24, max_iters=80, stall_iters=25)


def fig2_dag(env):
    return LayerDAG(
        compute=np.array([1.1, 1.92, 2.35, 2.12]) * env.power[0],
        edges=np.array([[0, 1], [0, 2], [1, 3], [2, 3]]),
        edge_mb=np.array([1.0, 1.0, 0.5, 0.5]),
        app_id=np.zeros(4, np.int32), deadline=np.array([3.7]),
        pinned=np.array([0, -1, -1, -1], np.int32))


@pytest.fixture(scope="module")
def fleet3():
    """Three heterogeneous problems: different DAGs, envs, pins, deadlines."""
    env_s = sample_environment()
    env_p = paper_environment()
    return [(fig2_dag(env_s), env_s),
            (zoo.alexnet(pin_server=0, deadline=6.0), env_p),
            (zoo.vgg19(pin_server=1, deadline=40.0), env_p)]


# ---------------------------------------------------------------------------
# padded simulator == unpadded numpy oracle, regardless of padding amount
# ---------------------------------------------------------------------------

def test_padded_sim_matches_np_oracle(rng):
    """Fitness is invariant under (arbitrary) padding: the padded JAX sim
    reproduces the unpadded numpy oracle bit-for-bit in every field."""
    env = sample_environment()
    dag = zoo.alexnet(pin_server=0, deadline=6.0)
    prob = SimProblem.build(dag, env)
    pp = pad_problem(prob, max_p=32, max_S=11, max_in=4, max_out=5,
                     max_apps=3)
    for faithful in (True, False):
        for _ in range(5):
            x = rng.integers(0, env.num_servers, size=dag.num_layers)
            xp = np.zeros(32, np.int32)
            xp[:dag.num_layers] = x
            ref = simulate_np(prob, x, faithful=faithful)
            out = simulate_padded(pp, xp, faithful=faithful)
            np.testing.assert_allclose(
                np.asarray(out.end_times)[:dag.num_layers],
                ref.end_times, rtol=1e-6)
            np.testing.assert_allclose(float(out.total_cost),
                                       float(ref.total_cost), rtol=1e-6)
            assert bool(out.feasible) == bool(ref.feasible)
            np.testing.assert_allclose(float(out.makespan),
                                       float(ref.makespan), rtol=1e-6)
            # padded layers are no-ops: end time stays 0
            assert np.all(np.asarray(out.end_times)[dag.num_layers:] == 0.0)


# ---------------------------------------------------------------------------
# batched == sequential, gene for gene
# ---------------------------------------------------------------------------

def test_batched_matches_sequential(fleet3):
    """N=3 heterogeneous problems, same seeds: the batched fleet returns
    the sequential solver's gbest exactly — fitness, genes, iterations."""
    seeds = [0, 1, 2]
    seq = [run_pso_ga(dag, env, FAST, seed=s)
           for (dag, env), s in zip(fleet3, seeds)]
    bat = run_pso_ga_batch(fleet3, FAST, seed=seeds)
    for a, b in zip(seq, bat):
        assert a.best_fitness == b.best_fitness
        assert np.array_equal(a.best_x, b.best_x)
        assert a.iterations == b.iterations
        assert a.feasible == b.feasible
        assert a.best_cost == b.best_cost


def test_batched_scalar_seed_broadcasts(fleet3):
    one = run_pso_ga(*fleet3[0], FAST, seed=7)
    bat = run_pso_ga_batch(fleet3, FAST, seed=7)
    assert bat[0].best_fitness == one.best_fitness


# ---------------------------------------------------------------------------
# padding masks: padded layers / servers are never selected
# ---------------------------------------------------------------------------

def test_padding_never_selected(fleet3):
    results, state = run_pso_ga_batch(fleet3, FAST, seed=0,
                                      return_state=True)
    X = np.asarray(state.X)                    # (N, P, max_p)
    gbest = np.asarray(state.gbest_x)
    for i, (dag, env) in enumerate(fleet3):
        p, s = dag.num_layers, env.num_servers
        # real genes only ever name real servers (padded servers would be
        # unreachable: link_ok false, power 1)
        assert np.all(X[i, :, :p] < s)
        assert np.all(gbest[i, :p] < s)
        assert np.all(results[i].best_x < s)
        # padded genes were never mutated away from their init value 0
        assert np.all(X[i, :, p:] == 0)
        assert np.all(gbest[i, p:] == 0)
        assert results[i].best_x.shape == (p,)


def test_pack_problems_buckets_shapes(fleet3):
    ppb = pack_problems(fleet3, bucket=True)
    n_layers = max(d.num_layers for d, _ in fleet3)
    n_srv = max(e.num_servers for _, e in fleet3)
    assert ppb.compute.shape == (3, bucket_size(n_layers))
    assert ppb.power.shape[1] == bucket_size(n_srv, floor=4)
    assert np.array_equal(np.asarray(ppb.num_layers),
                          [d.num_layers for d, _ in fleet3])
    # padded deadlines are +inf -> never violated
    assert np.all(np.isinf(np.asarray(ppb.deadline)[:, 1:]))


def test_bucket_size():
    assert bucket_size(3) == 8
    assert bucket_size(8) == 8
    assert bucket_size(9) == 16
    assert bucket_size(341) == 512
    assert bucket_size(3, floor=4) == 4


# ---------------------------------------------------------------------------
# convergence freeze: an early-converged problem stops evolving
# ---------------------------------------------------------------------------

def test_convergence_freeze(fleet3):
    """A trivially-converged problem (everything pinned home, gbest found
    at init) freezes at stall_iters while harder problems keep iterating —
    and its frozen gbest equals its sequential solution."""
    env = paper_environment()
    alex = zoo.alexnet(pin_server=0, deadline=1e9)
    trivial = LayerDAG(compute=alex.compute, edges=alex.edges,
                       edge_mb=alex.edge_mb, app_id=alex.app_id,
                       deadline=alex.deadline,
                       pinned=np.zeros(alex.num_layers, np.int32))
    hard_dag, hard_env = fleet3[0]
    results = run_pso_ga_batch([(trivial, env), (hard_dag, hard_env)],
                               FAST, seed=0)
    triv, hard = results
    seq = run_pso_ga(trivial, env, FAST, seed=0)
    # converged immediately: gbest never improved after init, so the stall
    # counter ran straight to the stopping rule
    assert triv.iterations == FAST.stall_iters
    assert triv.best_fitness == seq.best_fitness == 0.0
    assert np.array_equal(triv.best_x, seq.best_x)
    # the harder problem kept iterating after the trivial one froze
    assert hard.iterations > triv.iterations
    # and matches ITS sequential run too (freeze leaked nothing across)
    seq_hard = run_pso_ga(hard_dag, hard_env, FAST, seed=0)
    assert hard.best_fitness == seq_hard.best_fitness


def test_runner_cache_reused(fleet3):
    from repro.core.batch import runner_cache_info
    run_pso_ga_batch(fleet3, FAST, seed=0)
    n_before = len(runner_cache_info())
    run_pso_ga_batch(fleet3, FAST, seed=3)     # same shapes, new seeds
    assert len(runner_cache_info()) == n_before


def test_batch_seed_count_mismatch(fleet3):
    with pytest.raises(ValueError):
        run_pso_ga_batch(fleet3, FAST, seed=[0, 1])


def test_batch_seed_int_like_scalars(fleet3):
    """np.int64 / 0-d arrays broadcast like python ints (regression:
    np.isscalar rejects 0-d arrays, so these used to crash or misfire)."""
    ref = run_pso_ga_batch(fleet3, FAST, seed=7)
    for seed in (np.int64(7), np.array(7), np.asarray(7, np.int32)):
        out = run_pso_ga_batch(fleet3, FAST, seed=seed)
        for a, b in zip(ref, out):
            assert a.best_fitness == b.best_fitness
            assert np.array_equal(a.best_x, b.best_x)


def test_batch_seed_array_sequence(fleet3):
    """Per-problem seeds as a numpy array behave like the list form."""
    ref = run_pso_ga_batch(fleet3, FAST, seed=[3, 4, 5])
    out = run_pso_ga_batch(fleet3, FAST, seed=np.array([3, 4, 5]))
    for a, b in zip(ref, out):
        assert a.best_fitness == b.best_fitness


def test_batch_seed_rejects_non_int(fleet3):
    with pytest.raises(TypeError):
        run_pso_ga_batch(fleet3, FAST, seed=0.5)
    with pytest.raises(ValueError):
        run_pso_ga_batch(fleet3, FAST, seed=np.zeros((2, 2), np.int32))


# ---------------------------------------------------------------------------
# grouped swarm init == the unbatched init_swarm, problem by problem
# ---------------------------------------------------------------------------

INIT_CFG = PSOGAConfig(pop_size=40)
# problems: three alexnets (one (p, S) group of 3 — not a power of two),
# one vgg19 on the same environment, and the Fig. 2 DAG on the 6-server
# sample environment
INIT_SEEDS = [5, 2**31 + 5, -7919, 2**32 + 5, 11]


@pytest.fixture(scope="module")
def init_fleet():
    env_p, env_s = paper_environment(), sample_environment()
    dags = [(zoo.alexnet(pin_server=k, deadline=6.0), env_p)
            for k in range(3)]
    dags += [(zoo.vgg19(pin_server=1, deadline=40.0), env_p),
             (fig2_dag(env_s), env_s)]
    return [SimProblem.build(d, e) for d, e in dags]


def _reference_init(b, probs, seeds, cfg, incumbent, mig, rescue):
    """The per-problem loop the grouped init replaces, over the public
    unbatched ``init_swarm``."""
    import jax
    from repro.core.pso_ga import init_swarm
    nb = len(b.idx)
    keys = np.zeros((nb, 2), np.uint32)
    X0b = np.zeros((nb, cfg.pop_size, b.max_p), np.int32)
    incb = np.zeros((nb, b.max_p), np.int32)
    migb = np.zeros((nb,), np.float32)
    for j, i in enumerate(b.idx):
        p = probs[i].num_layers
        key, k_init = jax.random.split(jax.random.PRNGKey(seeds[i]))
        keys[j] = np.asarray(key)
        inc = None if incumbent is None else incumbent[i]
        if inc is not None:
            incb[j, :p] = inc
            migb[j] = mig[i]
        X0b[j, :, :p] = np.asarray(init_swarm(
            k_init, probs[i], cfg, incumbent=inc,
            rescue=inc is not None and rescue is not None and rescue[i]))
    return keys, X0b, incb, migb


# (problem subset, bucketed?, incumbents?, rescue flags, demoted index)
INIT_CASES = {
    "cold": ([0, 1, 2, 3, 4], True, False, None, None),
    "warm": ([0, 1, 2, 3, 4], True, True, None, None),
    "warm_rescue": ([0, 1, 2, 3, 4], True, True,
                    [True, False, True, False, True], None),
    "warm_demoted": ([0, 1, 2, 3, 4], True, True,
                     [True, False, True, False, True], 2),
    "two_groups": ([0, 1, 3], False, True, [False, True, True], None),
    "non_pow2": ([0, 1, 2], True, False, None, None),
}


@pytest.mark.parametrize("case", list(INIT_CASES))
def test_grouped_init_matches_init_swarm(case, init_fleet):
    from repro.core.batch import _init_bucket, pack_fleet
    sel, bucketed, warm, rescue, demoted = INIT_CASES[case]
    probs = [init_fleet[k] for k in sel]
    seeds = [INIT_SEEDS[k] for k in sel]
    rng = np.random.default_rng(3)
    incumbent = None
    if warm:
        incumbent = [rng.integers(0, pr.num_servers, pr.num_layers)
                     .astype(np.int32) for pr in probs]
        if demoted is not None:
            incumbent[demoted] = None
    mig = np.linspace(0.5, 1.5, len(probs)).astype(np.float32)
    fleet = pack_fleet(probs, bucket=bucketed)
    n_groups = []
    for b in fleet.buckets:
        *got, groups = _init_bucket(b, probs, seeds, INIT_CFG, incumbent,
                                    mig, rescue)
        n_groups.append(groups)
        want = _reference_init(b, probs, seeds, INIT_CFG, incumbent, mig,
                               rescue)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(g, w)
    if case == "two_groups":
        assert n_groups == [2]
    if case == "non_pow2":
        assert n_groups == [1] and len(fleet.buckets[0].idx) == 3


def _eager_init_swarm(key, prob, cfg, incumbent=None, rescue=False):
    """The eager ``init_swarm`` the traced core replaced: the same draws,
    with one ``.at[].set`` per anchor row."""
    import jax
    import jax.numpy as jnp
    from repro.core.pso_ga import _home_servers
    p, s, P = prob.num_layers, prob.num_servers, cfg.pop_size
    home = _home_servers(prob)
    allowed = np.asarray(prob.link_ok)[home, :].copy()
    allowed[np.arange(p), home] = True
    logits = jnp.where(jnp.asarray(allowed), 0.0, -jnp.inf)
    k1, k_warm = jax.random.split(key)
    X = jax.random.categorical(k1, logits, axis=-1,
                               shape=(P, p)).astype(jnp.int32)
    if incumbent is not None:
        inc = jnp.asarray(incumbent, jnp.int32)
        n_elite = max(1, min(cfg.warm_elite, P))
        n_neigh = min(int(round(cfg.warm_fraction * P)), P - n_elite)
        X = X.at[:n_elite].set(inc[None, :])
        if n_neigh > 0:
            k_mask, k_val = jax.random.split(k_warm)
            mut = jax.random.uniform(k_mask, (n_neigh, p)) \
                < cfg.warm_mutation
            vals = jax.random.categorical(
                k_val, logits, axis=-1, shape=(n_neigh, p)).astype(jnp.int32)
            X = X.at[n_elite:n_elite + n_neigh].set(
                jnp.where(mut, vals, inc[None, :]))
        tail = n_elite + n_neigh
        if rescue and cfg.bias_init_to_tiers and tail < P:
            X = X.at[tail].set(jnp.asarray(home))
            by_power = np.argsort(-np.asarray(prob.power), kind="stable")
            for k in range(min(s + 1, P - tail) - 1):
                X = X.at[tail + 1 + k].set(
                    jnp.full((p,), int(by_power[k]), jnp.int32))
    elif cfg.bias_init_to_tiers:
        X = X.at[0].set(jnp.asarray(home))
        for k in range(min(s + 1, P - 1) - 1):
            X = X.at[1 + k].set(jnp.full((p,), k, jnp.int32))
    pinned = jnp.asarray(prob.pinned)
    return jnp.where(pinned[None, :] >= 0, pinned[None, :], X)


# (cfg, incumbent?, rescue?): the population spans fewer and more rows
# than the anchors need, so both anchor blocks get cut short somewhere
CORE_CASES = {
    "cold": (INIT_CFG, False, False),
    "cold_short": (PSOGAConfig(pop_size=8), False, False),
    "cold_unbiased": (PSOGAConfig(pop_size=40, bias_init_to_tiers=False),
                      False, False),
    "warm": (INIT_CFG, True, False),
    "rescue": (INIT_CFG, True, True),
    "rescue_short": (PSOGAConfig(pop_size=30), True, True),
    "rescue_no_tail": (PSOGAConfig(pop_size=10, warm_fraction=0.9),
                       True, True),
}


@pytest.mark.parametrize("case", list(CORE_CASES))
def test_init_swarm_matches_eager_anchor_loops(case, init_fleet):
    """``init_swarm`` (the traced core, anchors by row index) equals the
    eager per-anchor loop it replaced, bit for bit."""
    import jax
    from repro.core.pso_ga import init_swarm
    cfg, warm, rescue = CORE_CASES[case]
    rng = np.random.default_rng(5)
    for k, prob in enumerate(init_fleet):
        key = jax.random.PRNGKey(INIT_SEEDS[k])
        inc = rng.integers(0, prob.num_servers, prob.num_layers) \
            if warm else None
        got = np.asarray(init_swarm(key, prob, cfg, incumbent=inc,
                                    rescue=rescue))
        want = np.asarray(_eager_init_swarm(key, prob, cfg, incumbent=inc,
                                            rescue=rescue))
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("x64", [False, True])
@pytest.mark.parametrize("seed", [0, 1, 2**31 - 1, 2**31, 2**32 + 5, -1,
                                  -7919])
def test_root_keys_match_prngkey(seed, x64):
    """Host-built key words equal ``jax.random.PRNGKey(seed)``: without
    x64 JAX keeps only the seed's low 32 bits, so a naive
    ``[seed >> 32, seed & mask]`` is wrong for the last three seeds."""
    import jax
    from repro.core.batch import _root_keys
    with jax.enable_x64(x64):
        want = np.asarray(jax.random.PRNGKey(seed))
        got = _root_keys([seed])[0]
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)


def test_grouped_init_not_retraced(init_fleet):
    """The init program traces once per (cfg, group shape, padded
    count): a repeat fleet, a warm round of it, and a fleet whose group
    counts round to the same power of two all reuse it."""
    from repro.core.batch import _INIT_STATS, _init_bucket, pack_fleet
    cfg = PSOGAConfig(pop_size=40, warm_elite=3)   # a cfg of its own

    def init(sel, warm=False):
        probs = [init_fleet[k] for k in sel]
        inc = [np.zeros(pr.num_layers, np.int32) for pr in probs] \
            if warm else None
        b, = pack_fleet(probs).buckets
        _init_bucket(b, probs, list(range(len(probs))), cfg, inc,
                     np.ones(len(probs), np.float32), None)

    before = _INIT_STATS["traces"]
    init([0, 1, 2])                            # 3 alexnets, padded to 4
    traced = _INIT_STATS["traces"]
    assert traced == before + 1
    init([0, 1, 2])
    init([0, 1, 2], warm=True)
    init([0, 1, 2, 0])                         # 4 rounds to 4 as well
    assert _INIT_STATS["traces"] == traced
    init([0, 1])                               # 2: a new padded count
    assert _INIT_STATS["traces"] == traced + 1


def test_swarm_init_groups_telemetry(init_fleet):
    """``swarm_init`` spans carry their bucket's group count and the
    ``swarm_init.groups`` counter sums them."""
    from repro.core.telemetry import Telemetry
    tel = Telemetry()
    probs = [init_fleet[k] for k in (0, 1, 3)]
    run_pso_ga_batch(probs, FAST, seed=0, bucket=False, telemetry=tel)
    spans = [e for e in tel.tracer.events()
             if e["ph"] == "B" and e["name"] == "swarm_init"]
    assert [s["args"]["groups"] for s in spans] == [2]
    assert tel.registry.snapshot()["counters"]["swarm_init.groups"] == 2
