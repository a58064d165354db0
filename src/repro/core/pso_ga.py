"""PSO-GA — self-adaptive discrete PSO with GA operators (paper §IV-B).

The particle position is the server-assignment vector (the order genes φ
are frozen to the topological order at init — §IV-B.3). One iteration
applies, per particle (Eq. 17–20):

    A = w  ⊕ Mu(X)            mutation       (inertia component)
    B = c1 ⊕ Cp(A, pBest)     crossover      (individual cognition)
    C = c2 ⊕ Cg(B, gBest)     crossover      (social cognition)

with the self-adaptive inertia weight (Eq. 22–23)

    w = w_max − (w_max − w_min) · exp(d / (d − 1.01)),
    d = div(gBest, X) / p_dims       (fraction of differing genes)

(d→0 ⇒ w→w_min: converged particles mutate rarely; d→1 ⇒ w→w_max).
Acceleration coefficients ramp linearly: c1 0.9→0.2, c2 0.4→0.9 [34].

The whole swarm advances in one jitted step: fitness is the swarm-level
Algorithm-2 simulator (``fitness.make_swarm_fitness`` — two-phase scan
or the Pallas replay kernel, per ``PSOGAConfig.fitness_backend``,
DESIGN.md §8), mutation/crossover are vectorized index ops, and the
iteration loop is a ``lax.while_loop`` with the paper's stopping rule
(terminate when gBest is unchanged for ``stall_iters`` iterations, or at
``max_iters``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .dag import LayerDAG
from .environment import Environment
from .fitness import make_swarm_fitness
from .simulator import (PaddedProblem, SimProblem, build_simulator,
                        pad_problem)
from .telemetry import get_telemetry

__all__ = ["PSOGAConfig", "PSOGAResult", "run_pso_ga", "init_swarm",
           "swarm_step"]


@dataclasses.dataclass(frozen=True)
class PSOGAConfig:
    pop_size: int = 100
    max_iters: int = 1000
    stall_iters: int = 50           # paper §V-C: stop after 50 unchanged
    w_max: float = 0.9
    w_min: float = 0.4
    c1_start: float = 0.9
    c1_end: float = 0.2
    c2_start: float = 0.4
    c2_end: float = 0.9
    faithful_sim: bool = False      # False = parent-gated recurrence, which
    #   matches the paper's own worked example (Fig. 2: 3.41 s / 3.65 s /
    #   ">4 s" are only reproduced with parent gating); True = the printed
    #   Alg. 2 line-21 recurrence verbatim (see DESIGN.md §2).
    bias_init_to_tiers: bool = True  # seed swarm with tier-aware particles
    fitness_backend: str = "scan"   # scan | pallas | auto (DESIGN.md §8):
    #   "scan" = two-phase simulate_padded under vmap (bit-exact default);
    #   "pallas" = kernels/schedule_sim tile kernel (interpret off-TPU);
    #   "auto" = pallas on TPU, scan elsewhere.
    # -- incumbent ("warm") seeding, used by online re-planning
    #    (DESIGN.md §9); only consulted when init_swarm gets an incumbent.
    warm_elite: int = 2             # exact clones of the incumbent plan
    warm_fraction: float = 0.5      # swarm share seeded in the incumbent's
    #   mutated neighborhood (per-gene redraw with prob warm_mutation)
    warm_mutation: float = 0.1      # per-gene neighborhood redraw prob
    # -- contention-aware fitness (DESIGN.md §10); only consulted when a
    #    solve is handed Monte-Carlo ``arrivals``: the p95 deadline-miss
    #    budget the plan must satisfy under the request stream.
    miss_budget: float = 0.05


class PSOGAResult(NamedTuple):
    best_x: np.ndarray           # (p,) best server assignment found
    best_fitness: float          # scalar key (cost if feasible)
    best_cost: float             # C_total of best (inf if infeasible)
    feasible: bool
    iterations: int              # iterations actually executed
    history: Optional[np.ndarray] = None  # (max_iters,) gBest key per iter


class _SwarmState(NamedTuple):
    key: jnp.ndarray
    X: jnp.ndarray               # (P, p) int32
    pbest_x: jnp.ndarray         # (P, p)
    pbest_f: jnp.ndarray         # (P,)
    gbest_x: jnp.ndarray         # (p,)
    gbest_f: jnp.ndarray         # ()
    it: jnp.ndarray              # ()
    stall: jnp.ndarray           # ()


def _clamp_pins(X: jnp.ndarray, pinned: jnp.ndarray) -> jnp.ndarray:
    return jnp.where(pinned[None, :] >= 0, pinned[None, :], X)


def _home_servers(prob: SimProblem) -> np.ndarray:
    """Per-layer home server: the pinned server of the layer's app (or 0)."""
    pin_per_app = {}
    pinned_np = np.asarray(prob.pinned)
    app_np = np.asarray(prob.app_id)
    for j in range(prob.num_layers):
        if pinned_np[j] >= 0:
            pin_per_app.setdefault(int(app_np[j]), int(pinned_np[j]))
    return np.array([pin_per_app.get(int(a), 0) for a in app_np], np.int32)


def _init_arrays(prob: SimProblem
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """The host half of ``init_swarm``: numpy ``(home (p,), allowed (p, S),
    pinned (p,), by_power (S,))`` — each layer's home server, the servers
    a gene may start on, the pins, and the servers by DESCENDING power
    (the rescue anchors' order)."""
    p = prob.num_layers
    home = _home_servers(prob)
    allowed = np.asarray(prob.link_ok)[home, :]      # (p, S), a copy
    allowed[np.arange(p), home] = True
    pinned = np.asarray(prob.pinned, np.int32)
    by_power = np.argsort(-np.asarray(prob.power),
                          kind="stable").astype(np.int32)
    return home, allowed, pinned, by_power


def _anchor_rows(X: jnp.ndarray, home: jnp.ndarray, start: int,
                 n_anchor: int, singles: jnp.ndarray) -> jnp.ndarray:
    """``X`` with row ``start`` set to ``home`` and row ``start+1+k`` to
    the single-server placement ``singles[k]`` for ``k < n_anchor-1``."""
    row = jnp.arange(X.shape[0])[:, None]                       # (P, 1)
    k = row - (start + 1)
    single = singles[jnp.clip(k, 0, singles.shape[0] - 1)]      # (P, 1)
    X = jnp.where((k >= 0) & (k < n_anchor - 1), single, X)
    return jnp.where(row == start, home[None, :], X)


def _init_core(k_init: jax.Array, home: jnp.ndarray, allowed: jnp.ndarray,
               pinned: jnp.ndarray, by_power: jnp.ndarray,
               inc: jnp.ndarray, has_inc: jnp.ndarray, rescue: jnp.ndarray,
               cfg: PSOGAConfig) -> jnp.ndarray:
    """The traceable half of ``init_swarm``: ``(P, p)`` int32 genes.

    Static in ``cfg`` and the shapes ``(p, S)`` of ``allowed``; the
    incumbent ``inc (p,)`` and the flags ``has_inc`` / ``rescue`` are
    traced, so one program serves cold, warm and rescue problems alike
    (``batch`` vmaps it over a group of same-shape problems). Every
    draw is made in every mode, from the same key splits and at the
    same shapes as a solve of that mode alone, and the flags pick among
    the finished swarms: picking never changes a drawn bit."""
    P = cfg.pop_size
    p, s = allowed.shape
    logits = jnp.where(allowed, 0.0, -jnp.inf)                  # (p, S)
    k1, k_warm = jax.random.split(k_init)
    # categorical broadcasts logits over the requested sample shape: the
    # gumbel draw is (P, p, S) either way, so this samples bit-identically
    # to materializing a (P, p, S) logits tensor — without the copy.
    X = jax.random.categorical(
        k1, logits, axis=-1, shape=(P, p)).astype(jnp.int32)
    cold = X
    if cfg.bias_init_to_tiers:
        # Warm-start anchors (standard metaheuristic practice; ≤ S+1 of the
        # swarm): the all-home placement (the paper's loose-deadline
        # limiting solution) and the S single-server placements. The
        # remaining ~P−S−1 particles stay random — diversity is preserved
        # and every anchor can be displaced by a fitter random particle.
        cold = _anchor_rows(X, home, 0, min(s + 1, P - 1),
                            jnp.arange(s, dtype=jnp.int32))
    n_elite = max(1, min(cfg.warm_elite, P))
    n_neigh = min(int(round(cfg.warm_fraction * P)), P - n_elite)
    warm = X.at[:n_elite].set(inc[None, :])
    if n_neigh > 0:
        k_mask, k_val = jax.random.split(k_warm)
        mut = jax.random.uniform(k_mask, (n_neigh, p)) < cfg.warm_mutation
        vals = jax.random.categorical(
            k_val, logits, axis=-1, shape=(n_neigh, p)).astype(jnp.int32)
        warm = warm.at[n_elite:n_elite + n_neigh].set(
            jnp.where(mut, vals, inc[None, :]))
    tail = n_elite + n_neigh
    if cfg.bias_init_to_tiers and tail < P:
        warm = jnp.where(rescue, _anchor_rows(
            warm, home, tail, min(s + 1, P - tail), by_power), warm)
    return _clamp_pins(jnp.where(has_inc, warm, cold), pinned)


_init_one = jax.jit(_init_core, static_argnames="cfg")


def init_swarm(key: jax.Array, prob: SimProblem, cfg: PSOGAConfig,
               incumbent: Optional[np.ndarray] = None,
               rescue: bool = False) -> jnp.ndarray:
    """Link-aware random initialization.

    Genes are drawn uniformly over the servers *reachable from the app's
    home device* ({home} ∪ {s : ℓ(home, s) > 0}) so the initial swarm has
    zero forbidden-link placements. Mutation still draws from ALL servers,
    so the full space remains reachable — this is a search-space seeding
    choice the paper leaves unspecified, not a restriction of the encoding
    (see EXPERIMENTS.md §Perf for its ablation). One particle is seeded
    with the everything-stays-home placement: the paper's own limiting
    solution (zero cost when the deadline is loose, Fig. 8(b)).

    With ``incumbent`` (a (p,) assignment — online re-planning,
    DESIGN.md §9) the seeding switches to incumbent mode:
    ``cfg.warm_elite`` exact clones of the incumbent, then
    ``cfg.warm_fraction`` of the swarm in its mutated neighborhood
    (per-gene redraw with prob ``cfg.warm_mutation`` from the link-aware
    allowed set), and the remaining particles keep the cold random draw
    for diversity. ``rescue=True`` (the re-planner sets it per problem
    when drift has stranded the incumbent infeasible — node-loss, heavy
    congestion) additionally re-applies the cold tier anchors at the
    tail, single-server placements ordered by DESCENDING power so the
    strongest escape hatches survive tail truncation: recovering
    feasibility then starts from the same anchors a cold solve gets. A
    healthy incumbent skips the anchors — they only slow convergence
    toward a plan that is already near-optimal. The cold draw consumes
    the same key split either way, so passing ``incumbent=None`` is
    bit-identical to the pre-warm-start initialization.
    """
    has_inc = incumbent is not None
    inc = (np.asarray(incumbent, np.int32) if has_inc
           else np.zeros(prob.num_layers, np.int32))
    return _init_one(key, *_init_arrays(prob), inc, has_inc,
                     has_inc and rescue, cfg=cfg)


def swarm_step(pp: PaddedProblem, state: _SwarmState,
               cfg: PSOGAConfig,
               incumbent: Optional[jnp.ndarray] = None,
               mig_weight: Optional[jnp.ndarray] = None,
               arrivals: Optional[jnp.ndarray] = None) -> _SwarmState:
    """One PSO-GA iteration on the padded representation (Eq. 17–23).

    Pure in ``(pp, state)`` — ``repro.core.batch`` vmaps it over a fleet of
    problems. Mutation/crossover positions and mutation values draw their
    bounds from ``pp.num_layers`` / ``pp.num_servers`` (the TRUE sizes,
    traced per problem under vmap), so a padded layer is never mutated and
    a padded server is never proposed: padded genes stay at their initial
    value and padding is invisible to the search (DESIGN.md §4).

    ``incumbent`` / ``mig_weight`` (both traceable arrays) switch the
    fitness to the migration-aware warm key (DESIGN.md §9); a zero
    ``mig_weight`` reproduces the cold key bit-for-bit. ``arrivals``
    (``(M, max_apps, R)``, traceable) switches it to the queue-aware
    traffic key under ``cfg.miss_budget`` (DESIGN.md §10).
    """
    max_p = pp.pinned.shape[-1]
    p = pp.num_layers                 # true sizes; 0-d, traced under vmap
    s = pp.num_servers
    P = cfg.pop_size
    fit = make_swarm_fitness(pp, cfg.faithful_sim, cfg.fitness_backend,
                             incumbent=incumbent, mig_weight=mig_weight,
                             arrivals=arrivals,
                             miss_budget=cfg.miss_budget)

    key, kmu, kmu_pos, kmu_val, kc1, kx1, kc2, kx2 = jax.random.split(
        state.key, 8)
    t = state.it.astype(jnp.float32) / cfg.max_iters
    c1 = cfg.c1_start + (cfg.c1_end - cfg.c1_start) * t
    c2 = cfg.c2_start + (cfg.c2_end - cfg.c2_start) * t

    # --- adaptive inertia (Eq. 22-23): per-particle w from divergence.
    # Padded genes never differ from gBest's (both frozen at init value),
    # so the sum only counts real genes; divide by the TRUE gene count.
    d = jnp.sum((state.X != state.gbest_x[None, :]).astype(jnp.float32),
                axis=1) / p.astype(jnp.float32)                # (P,)
    w = cfg.w_max - (cfg.w_max - cfg.w_min) * jnp.exp(d / (d - 1.01))

    # --- inertia: mutation Mu with prob w (Eq. 20)
    do_mu = jax.random.uniform(kmu, (P,)) < w
    pos = jax.random.randint(kmu_pos, (P,), 0, p)
    val = jax.random.randint(kmu_val, (P,), 0, s, dtype=jnp.int32)
    A = jnp.where(
        (jnp.arange(max_p)[None, :] == pos[:, None]) & do_mu[:, None],
        val[:, None], state.X)

    # --- individual cognition: crossover with pBest (Eq. 18)
    do_c1 = jax.random.uniform(kc1, (P,)) < c1
    seg1 = jax.random.randint(kx1, (P, 2), 0, p)
    lo1 = jnp.min(seg1, axis=1)[:, None]
    hi1 = jnp.max(seg1, axis=1)[:, None]
    in_seg1 = (jnp.arange(max_p)[None, :] >= lo1) \
        & (jnp.arange(max_p)[None, :] <= hi1)
    B = jnp.where(in_seg1 & do_c1[:, None], state.pbest_x, A)

    # --- social cognition: crossover with gBest (Eq. 19)
    do_c2 = jax.random.uniform(kc2, (P,)) < c2
    seg2 = jax.random.randint(kx2, (P, 2), 0, p)
    lo2 = jnp.min(seg2, axis=1)[:, None]
    hi2 = jnp.max(seg2, axis=1)[:, None]
    in_seg2 = (jnp.arange(max_p)[None, :] >= lo2) \
        & (jnp.arange(max_p)[None, :] <= hi2)
    C = jnp.where(in_seg2 & do_c2[:, None], state.gbest_x[None, :], B)

    X = _clamp_pins(C, pp.pinned)
    f = fit(X)

    improved = f < state.pbest_f
    pbest_x = jnp.where(improved[:, None], X, state.pbest_x)
    pbest_f = jnp.where(improved, f, state.pbest_f)
    i_best = jnp.argmin(pbest_f)
    cand_f = pbest_f[i_best]
    better = cand_f < state.gbest_f
    gbest_x = jnp.where(better, pbest_x[i_best], state.gbest_x)
    gbest_f = jnp.where(better, cand_f, state.gbest_f)
    stall = jnp.where(better, 0, state.stall + 1)
    return _SwarmState(key=key, X=X, pbest_x=pbest_x, pbest_f=pbest_f,
                       gbest_x=gbest_x, gbest_f=gbest_f,
                       it=state.it + 1, stall=stall)


def _make_step(prob: SimProblem, cfg: PSOGAConfig,
               arrivals: Optional[np.ndarray] = None):
    """Unbatched (zero-padding) step + swarm-fitness for one problem."""
    pp = pad_problem(prob)
    arr = None if arrivals is None else jnp.asarray(arrivals)
    fit = make_swarm_fitness(pp, cfg.faithful_sim, cfg.fitness_backend,
                             arrivals=arr, miss_budget=cfg.miss_budget)
    return partial(swarm_step, pp, cfg=cfg, arrivals=arr), fit


def run_pso_ga(dag: LayerDAG, env: Environment,
               cfg: PSOGAConfig = PSOGAConfig(),
               seed: int = 0,
               record_history: bool = False,
               arrivals: Optional[np.ndarray] = None,
               telemetry=None) -> PSOGAResult:
    """Run PSO-GA to convergence. Returns the best assignment found.

    ``arrivals`` (``(M, n_apps, R)`` Monte-Carlo request timestamps,
    DESIGN.md §10) switches the fitness to the queue-aware traffic key:
    ``best_fitness`` is then the traffic key (seed-mean load-adjusted
    cost when the p95 miss budget is met); ``best_cost`` / ``feasible``
    still report the zero-load replay of the winning plan so results
    stay comparable across modes — use ``traffic.traffic_replay`` for
    the plan's load metrics.

    With ``record_history`` and a telemetry channel (explicit arg, or
    the process-global one from ``telemetry_scope``) the per-iteration
    gBest curve is published as the ``solver.gbest`` metric series
    (DESIGN.md §13) alongside the returned ``history`` array.
    """
    prob = SimProblem.build(dag, env)
    step, fit = _make_step(prob, cfg, arrivals=arrivals)
    key = jax.random.PRNGKey(seed)
    key, k_init = jax.random.split(key)
    X0 = init_swarm(k_init, prob, cfg)
    f0 = fit(X0)
    i0 = jnp.argmin(f0)
    state = _SwarmState(key=key, X=X0, pbest_x=X0, pbest_f=f0,
                        gbest_x=X0[i0], gbest_f=f0[i0],
                        it=jnp.asarray(0), stall=jnp.asarray(0))

    if record_history:
        def body(state, _):
            state = step(state)
            return state, state.gbest_f
        # scan traces (and the surrounding dispatch jit-compiles) the body
        # itself — wrapping it in jax.jit would only re-enter the jit
        # cache every iteration.
        state, hist = jax.lax.scan(body, state, None, length=cfg.max_iters)
        history = np.asarray(hist)
        iters = cfg.max_iters
        tel = telemetry if telemetry is not None else get_telemetry()
        if tel is not None:
            tel.record_series("solver.gbest", history)
            tel.inc("solver.history_runs")
    else:
        def cond(s: _SwarmState):
            return (s.it < cfg.max_iters) & (s.stall < cfg.stall_iters)
        state = jax.lax.while_loop(cond, step, state)
        history = None
        iters = int(state.it)

    sim = build_simulator(prob, faithful=cfg.faithful_sim)
    res = sim(state.gbest_x)
    feasible = bool(res.feasible)
    return PSOGAResult(
        best_x=np.asarray(state.gbest_x),
        best_fitness=float(state.gbest_f),
        best_cost=float(res.total_cost) if feasible else float("inf"),
        feasible=feasible,
        iterations=iters,
        history=history)
