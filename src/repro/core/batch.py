"""Fleet-scale batched PSO-GA: solve N heterogeneous offloading problems
in ONE jitted program per shape bucket (DESIGN.md §4, §12).

The sequential solver re-traces and re-compiles ``lax.while_loop`` per
problem — fatal when a production planner must place many (DAG, env)
pairs per second. This module packs N heterogeneous ``SimProblem``s into
a ``PackedFleet`` of power-of-two ``(max_p, max_S)`` shape buckets: each
bucket stacks its members into one ``PaddedProblem`` whose leaves carry
a leading problem axis (layers padded to the BUCKET's ``max_p``, servers
to its ``max_S``, with validity encoded so padded layers are zero-cost
no-ops and padded servers unreachable), then runs each bucket's fleet of
swarms as ``vmap``-over-problems of ``swarm_step`` inside ONE
``lax.while_loop``. Bucket rounding is per-group, not fleet-global, so a
mostly-small fleet with one resnet101 no longer pads every problem ~8×
(DESIGN.md §12); results are scattered back through each bucket's
original-index permutation, restoring input order exactly.

Convergence is tracked per problem: a problem whose stall counter hits
``cfg.stall_iters`` (or that reaches ``cfg.max_iters``) is *frozen* — its
whole swarm state passes through unchanged while the rest of the fleet
keeps iterating — so every problem's trajectory is exactly what the
sequential solver would have produced, and the loop exits when the last
problem converges.

Because each problem keeps its own PRNG key (seeded exactly like
``run_pso_ga``), its own link-aware initial swarm, and mutation/crossover
bounds drawn from its TRUE ``(p, S)`` sizes, the batched solver matches
the sequential solver gene-for-gene in fitness — independent of which
bucket (or which co-tenants) a problem lands with (see
``tests/test_batch.py::test_batched_matches_sequential`` and the
bucket/permutation invariants in ``tests/test_fleet.py``).

With a ``mesh`` (``launch.mesh``), each bucket's runner is wrapped in a
``shard_map`` over the mesh's non-"model" axes: the problem axis splits
across the data shards (N padded up to a multiple of the shard count
with masked dummy problems — replicas of row 0 whose results are
discarded), each shard runs its own while_loop to local convergence, and
per-problem freezing makes the sharded solve gene-for-gene identical to
the single-device path (DESIGN.md §12).

Compiled programs are cached per ``(cfg, traffic?, shape-bucket, mesh)``,
with jit specializing on the exact ``(N, max_p, max_S, ...)`` shapes
underneath, so repeated fleets with similar shapes skip retracing
entirely.
"""
from __future__ import annotations

import dataclasses
import threading
from functools import partial
from typing import (Callable, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np

from .dag import LayerDAG
from .environment import Environment
from .fitness import make_swarm_fitness, resolve_fitness_backend
from .pso_ga import (PSOGAConfig, PSOGAResult, _SwarmState, _init_arrays,
                     _init_core, swarm_step)
from .seeding import coerce_seed
from .simulator import PaddedProblem, SimProblem, pad_problem, simulate_padded
from .telemetry import get_telemetry, maybe_span

__all__ = ["pack_problems", "pack_arrivals", "run_pso_ga_batch",
           "bucket_size", "FleetBucket", "PackedFleet", "pack_fleet",
           "runner_cache_info", "runner_cache_stats",
           "reset_runner_cache_stats"]

ProblemLike = Union[SimProblem, Tuple[LayerDAG, Environment]]


def bucket_size(n: int, floor: int = 8) -> int:
    """Round up to the next power of two (>= floor) — the shape bucket."""
    return max(floor, 1 << max(0, int(n) - 1).bit_length())


def _as_problems(problems: Sequence[ProblemLike]) -> List[SimProblem]:
    out = []
    for pr in problems:
        if isinstance(pr, SimProblem):
            out.append(pr)
        else:
            dag, env = pr
            out.append(SimProblem.build(dag, env))
    return out


def _normalize_seeds(seed, n: int) -> List[int]:
    """One seed per problem from any int-like scalar or sequence.

    ``np.isscalar`` is the wrong predicate here: it rejects 0-d numpy
    arrays (``np.array(7)``) and, on some numpy versions, numpy integer
    scalars — both of which flow naturally out of configs and RNGs. Treat
    anything 0-d as a broadcast scalar (via the shared ``coerce_seed``
    front door, so samplers and the fleet solver fail identically), any
    1-d integer-like sequence as per-problem seeds.
    """
    arr = np.asarray(seed)
    if not np.issubdtype(arr.dtype, np.integer):
        raise TypeError(f"seed must be int-like, got dtype {arr.dtype}")
    if arr.ndim == 0:
        return [coerce_seed(arr)] * n
    if arr.ndim != 1:
        raise ValueError(f"seed must be a scalar or 1-d sequence, "
                         f"got shape {arr.shape}")
    if arr.shape[0] != n:
        raise ValueError(f"{arr.shape[0]} seeds for {n} problems")
    return [int(s) for s in arr]


def pack_problems(problems: Sequence[ProblemLike],
                  bucket: bool = True) -> PaddedProblem:
    """Pack N heterogeneous problems into one stacked ``PaddedProblem``
    at a single fleet-global shape.

    Every leaf gains a leading ``N`` axis; per-problem true sizes live in
    the ``num_layers`` / ``num_servers`` / ``num_apps`` fields (shape
    (N,)). With ``bucket=True`` the layer/server axes round up to power-
    of-two buckets so fleets of similar shapes share compiled programs.

    This is the single-shape primitive — the fleet solver now groups
    problems into per-size buckets via ``pack_fleet`` instead of padding
    the whole fleet to the global max (DESIGN.md §12).
    """
    probs = _as_problems(problems)
    if not probs:
        raise ValueError("pack_problems needs at least one problem")
    max_p = max(pr.num_layers for pr in probs)
    max_S = max(pr.num_servers for pr in probs)
    if bucket:
        max_p, max_S = bucket_size(max_p), bucket_size(max_S, floor=4)
    max_in = max(pr.parent_idx.shape[1] for pr in probs)
    max_out = max(pr.child_idx.shape[1] for pr in probs)
    max_apps = max(pr.num_apps for pr in probs)
    padded = [pad_problem(pr, max_p=max_p, max_S=max_S, max_in=max_in,
                          max_out=max_out, max_apps=max_apps)
              for pr in probs]
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *padded)


class FleetBucket(NamedTuple):
    """One shape bucket of a ``PackedFleet``: the members stacked at the
    bucket's padded shape, plus their original fleet indices."""
    ppb: PaddedProblem           # stacked leaves, leading axis = len(idx)
    idx: np.ndarray              # (len,) original problem indices
    max_p: int                   # bucket layer padding (power of two)
    max_S: int                   # bucket server padding (power of two)


@dataclasses.dataclass(frozen=True)
class PackedFleet:
    """N heterogeneous problems grouped into ``(max_p, max_S)`` shape
    buckets (DESIGN.md §12). Bucket membership is a pure function of
    each problem's own true sizes — never of its co-tenants — so the
    same problem lands in the same bucket under any fleet permutation,
    and ``buckets[*].idx`` is the original→bucket permutation used to
    restore input order in results."""
    buckets: Tuple[FleetBucket, ...]
    n_problems: int
    max_apps: int                # fleet-global app padding (arrivals
    #   pack once per bucket against this shared width)


def pack_fleet(problems: Sequence[ProblemLike],
               bucket: bool = True) -> PackedFleet:
    """Group N problems into power-of-two ``(max_p, max_S)`` buckets.

    With ``bucket=True`` each problem's bucket is
    ``(bucket_size(p), bucket_size(S, floor=4))`` of its OWN true sizes —
    per-group rounding, so a fleet of mostly-small DNNs with one huge
    straggler pads only the straggler's bucket large. With
    ``bucket=False`` the whole fleet forms ONE bucket at the exact
    fleet-global ``(max p, max S)`` (the pre-§12 global-padding
    behavior, kept as the A/B baseline in ``bench_pso --mixed-fleet``).

    The in/out-degree and app paddings stay fleet-global: they are tiny
    axes, and a shared ``max_apps`` lets one ``pack_arrivals`` width
    serve every bucket.
    """
    probs = _as_problems(problems)
    if not probs:
        raise ValueError("pack_fleet needs at least one problem")
    max_in = max(pr.parent_idx.shape[1] for pr in probs)
    max_out = max(pr.child_idx.shape[1] for pr in probs)
    max_apps = max(pr.num_apps for pr in probs)
    if bucket:
        def key(pr: SimProblem) -> Tuple[int, int]:
            return (bucket_size(pr.num_layers),
                    bucket_size(pr.num_servers, floor=4))
    else:
        gp = max(pr.num_layers for pr in probs)
        gS = max(pr.num_servers for pr in probs)

        def key(pr: SimProblem) -> Tuple[int, int]:
            return (gp, gS)
    groups: Dict[Tuple[int, int], List[int]] = {}
    for i, pr in enumerate(probs):
        groups.setdefault(key(pr), []).append(i)
    buckets = []
    for bp, bS in sorted(groups):
        idx = np.asarray(groups[(bp, bS)], np.int64)
        padded = [pad_problem(probs[i], max_p=bp, max_S=bS, max_in=max_in,
                              max_out=max_out, max_apps=max_apps)
                  for i in idx]
        ppb = jax.tree.map(lambda *leaves: jnp.stack(leaves), *padded)
        buckets.append(FleetBucket(ppb=ppb, idx=idx, max_p=bp, max_S=bS))
    return PackedFleet(buckets=tuple(buckets), n_problems=len(probs),
                       max_apps=max_apps)


# --------------------------------------------------------------------------
# compiled fleet runner, cached per (cfg, traffic?, shape bucket, mesh)
# --------------------------------------------------------------------------

_RUNNER_CACHE: Dict[tuple, Callable] = {}
#: hits/misses count _fleet_runner lookups; traces counts actual jit
#: re-traces of the fleet loop (incremented from inside the traced body,
#: so it only ticks when XLA really recompiles — the online re-planning
#: invariant "every round after the first hits the compiled runner"
#: (DESIGN.md §9) is asserted against this counter.
_CACHE_STATS = {"hits": 0, "misses": 0, "traces": 0}
#: one lock guards lookups/inserts (and the counters) so N concurrent
#: ``run_service`` loops share one runner per key — the multi-service
#: invariant of DESIGN.md §11 phase 2.
_RUNNER_LOCK = threading.Lock()


def runner_cache_info() -> Tuple[tuple, ...]:
    """(config, traffic?, shape-bucket, mesh) keys currently holding a
    compiled fleet runner."""
    return tuple(_RUNNER_CACHE)


def runner_cache_stats() -> Dict[str, int]:
    """Snapshot of the fleet-runner cache counters (DESIGN.md §9)."""
    return dict(_CACHE_STATS)


def reset_runner_cache_stats() -> None:
    """Zero the counters (the compiled runners themselves are kept)."""
    for k in _CACHE_STATS:
        _CACHE_STATS[k] = 0


def _done(state: _SwarmState, cfg: PSOGAConfig) -> jnp.ndarray:
    """(N,) bool — which problems have hit the paper's stopping rule."""
    return (state.it >= cfg.max_iters) | (state.stall >= cfg.stall_iters)


def _mesh_cache_key(mesh) -> Optional[tuple]:
    """Hashable identity of a mesh for the runner cache: axis names,
    shape, and the device ids in mesh order (two mesh objects over the
    same devices in the same layout share compiled runners)."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat))


def _fleet_runner(cfg: PSOGAConfig, traffic: bool = False,
                  shape_bucket: Optional[Tuple[int, int]] = None,
                  mesh=None, telemetry=None) -> Callable:
    """Jitted ``(ppb, keys, X0b, incb, migb[, arrb]) -> final _SwarmState``.

    One cache entry per ``(cfg, traffic?, shape-bucket, mesh)`` (the
    config is baked into the traced loop; the traffic flag switches the
    runner's signature — with it, per-problem Monte-Carlo arrivals
    ``arrb (N, M, max_apps, R)`` ride along as one more traced argument,
    DESIGN.md §10; the shape bucket keys each ``(max_p, max_S)`` group
    of a ``PackedFleet`` to its own compiled program, DESIGN.md §12);
    jit's own cache handles exact shape specialization underneath, and
    the power-of-two buckets of ``pack_fleet`` keep the number of
    distinct shapes it sees small. Distinct bucket sizes N still trace
    separately — batch at stable sizes if that matters.

    Cold and warm (re-planning) solves share ONE program per bucket: the
    incumbent genes ``incb (N, max_p)`` and migration weights ``migb
    (N,)`` are ordinary traced arrays, and a zero weight multiplies the
    migration term away bit-exactly (DESIGN.md §9). Drift — of the
    environment OR of the arrival stream — only changes array *values*,
    so every re-planning round after the first reuses the compiled
    runner; ``runner_cache_stats()["traces"]`` counts the actual
    re-traces.

    With a ``mesh``, the runner body is wrapped in ``shard_map`` over
    the mesh's non-"model" axes before jitting: every input/output leaf
    shards its leading problem axis across the data shards, each shard
    runs its own while_loop to local convergence (per-problem freezing
    makes extra iterations no-ops, so shard-local exit is a pure win),
    and the caller guarantees N is a multiple of the shard count
    (``run_pso_ga_batch`` pads with masked dummy problems,
    DESIGN.md §12).

    The backend string is normalized BEFORE the cache key: ``"auto"``
    and whatever it resolves to on this host share one entry (and one
    compiled program), so flipping only the spelling of the backend
    never retraces — pinned by
    ``tests/test_traffic_kernel.py::test_runner_cache_backend_normalized``.

    Thread-safe: lookups, inserts, and the counters sit behind one lock,
    and first calls per shape specialization are serialized, so N
    concurrent ``run_service`` loops (``run_services``) get exactly one
    miss — and one trace — per key (DESIGN.md §11).
    """
    cfg = dataclasses.replace(
        cfg, fitness_backend=resolve_fitness_backend(cfg.fitness_backend))
    cache_key = (cfg, traffic, shape_bucket, _mesh_cache_key(mesh))
    with _RUNNER_LOCK:
        cached = _RUNNER_CACHE.get(cache_key)
        hit = cached is not None
        if hit:
            _CACHE_STATS["hits"] += 1
        else:
            _CACHE_STATS["misses"] += 1
            cached = _build_fleet_runner(cfg, traffic, mesh)
            _RUNNER_CACHE[cache_key] = cached
    # telemetry (DESIGN.md §13): explicit channel first, else the
    # process-global one — direct callers have no config path here.
    # Emitted outside the runner lock so the tracer's lock never nests
    # inside ours. Never part of the cache key: observation only.
    tel = telemetry if telemetry is not None else get_telemetry()
    if tel is not None:
        tel.inc("runner_cache.lookup_hits" if hit
                else "runner_cache.lookup_misses")
    return cached


def _serialize_first_calls(jitted: Callable) -> Callable:
    """Serialize the FIRST call per argument-shape specialization.

    ``jax.jit`` traces lazily at first invocation and drops the GIL
    while XLA compiles, so two service threads hitting a fresh runner
    could each trace the same program — double-counting the ``traces``
    invariant counter and compiling twice. One lock per shape signature
    makes the first call exclusive; warmed signatures take the lock-free
    fast path, so concurrent solves still overlap.
    """
    guard = threading.Lock()
    warmed: set = set()
    locks: Dict[tuple, threading.Lock] = {}

    def wrapper(*args):
        sig = tuple((tuple(leaf.shape), str(leaf.dtype))
                    for leaf in jax.tree.leaves(args)
                    if hasattr(leaf, "shape"))
        with guard:
            warm = sig in warmed
            lock = None if warm else locks.setdefault(sig, threading.Lock())
        if warm:
            return jitted(*args)
        with lock:
            out = jitted(*args)
        with guard:
            warmed.add(sig)
            locks.pop(sig, None)
        return out

    return wrapper


def _build_fleet_runner(cfg: PSOGAConfig, traffic: bool, mesh) -> Callable:
    """Construct (without tracing) the jitted fleet loop for
    ``_fleet_runner`` — see its docstring for the contract."""
    vstep = jax.vmap(lambda pp, st, inc, mw, arr: swarm_step(
        pp, st, cfg, incumbent=inc, mig_weight=mw, arrivals=arr))
    # one swarm-fitness per problem, vmapped over the fleet: the scan
    # backend batches the two-phase simulate_padded; the pallas backend's
    # grid picks up the problem axis as an outer grid dimension.
    vfit = jax.vmap(lambda pp, X, inc, mw, arr: make_swarm_fitness(
        pp, cfg.faithful_sim, cfg.fitness_backend,
        incumbent=inc, mig_weight=mw, arrivals=arr,
        miss_budget=cfg.miss_budget)(X))

    def run_impl(ppb: PaddedProblem, keys: jnp.ndarray, X0b: jnp.ndarray,
                 incb: jnp.ndarray, migb: jnp.ndarray,
                 arrb: Optional[jnp.ndarray]) -> _SwarmState:
        _CACHE_STATS["traces"] += 1        # python side effect: trace-time only
        n = X0b.shape[0]
        f0 = vfit(ppb, X0b, incb, migb, arrb)                  # (N, P)
        i0 = jnp.argmin(f0, axis=1)                            # (N,)
        gbest_x = jnp.take_along_axis(
            X0b, i0[:, None, None], axis=1)[:, 0, :]           # (N, max_p)
        gbest_f = jnp.take_along_axis(f0, i0[:, None], axis=1)[:, 0]
        state = _SwarmState(
            key=keys, X=X0b, pbest_x=X0b, pbest_f=f0,
            gbest_x=gbest_x, gbest_f=gbest_f,
            it=jnp.zeros((n,), jnp.int32), stall=jnp.zeros((n,), jnp.int32))

        def cond(st: _SwarmState) -> jnp.ndarray:
            return jnp.any(~_done(st, cfg))

        def body(st: _SwarmState) -> _SwarmState:
            new = vstep(ppb, st, incb, migb, arrb)
            frozen = _done(st, cfg)                            # (N,)
            return jax.tree.map(
                lambda nw, old: jnp.where(
                    frozen.reshape((-1,) + (1,) * (nw.ndim - 1)), old, nw),
                new, st)

        return jax.lax.while_loop(cond, body, state)

    # fixed arity per traffic flag: shard_map needs in_specs to match the
    # call signature exactly, so the no-traffic runner takes 5 args and
    # the traffic runner 6 (no optional-None juggling inside the spec).
    if traffic:
        def run(ppb, keys, X0b, incb, migb, arrb):
            return run_impl(ppb, keys, X0b, incb, migb, arrb)
    else:
        def run(ppb, keys, X0b, incb, migb):
            return run_impl(ppb, keys, X0b, incb, migb, None)

    if mesh is not None:
        from ..launch.mesh import data_axes_of
        # P((axes,)) shards dim 0 — the problem axis — over every
        # non-"model" axis jointly; the spec acts as a pytree prefix, so
        # each PaddedProblem/_SwarmState leaf splits its leading axis.
        spec = jax.sharding.PartitionSpec(tuple(data_axes_of(mesh)))
        n_args = 6 if traffic else 5
        run = jax.shard_map(run, mesh=mesh, in_specs=(spec,) * n_args,
                            out_specs=spec, check_vma=False)

    return _serialize_first_calls(jax.jit(run))


def pack_arrivals(arrivals: Sequence[np.ndarray],
                  max_apps: int) -> np.ndarray:
    """Stack per-problem ``(M, n_apps_i, R)`` Monte-Carlo arrival arrays
    into one ``(N, M, max_apps, R)`` traced input, padding the app axis
    with +inf (a padded app never receives a request — the same masked
    no-op discipline as padded layers, DESIGN.md §10). Every problem
    must share the seed count M and the request cap R (one compiled
    runner serves the fleet)."""
    mats = [np.asarray(a, float) for a in arrivals]
    if not mats:
        raise ValueError("pack_arrivals needs at least one arrival set")
    for i, a in enumerate(mats):
        if a.ndim != 3:
            raise ValueError(
                f"arrivals[{i}] has shape {a.shape}; expected a 3-d "
                f"(M, n_apps, R) Monte-Carlo array")
    m0, r0 = mats[0].shape[0], mats[0].shape[2]
    for i, a in enumerate(mats):
        if a.shape[0] != m0 or a.shape[2] != r0:
            raise ValueError(
                f"arrivals[{i}] has shape {a.shape}; expected (M={m0}, "
                f"n_apps, R={r0}) with M and R shared across the fleet")
        if a.shape[1] > max_apps:
            raise ValueError(f"arrivals[{i}] has {a.shape[1]} apps > "
                             f"packed max_apps {max_apps}")
        # +inf is the legal "no more requests" pad; NaN or negative
        # timestamps are corrupt draws and must not reach the kernel
        # (where they'd silently poison every merged-order replay).
        if np.isnan(a).any() or (a < 0.0).any():
            raise ValueError(f"arrivals[{i}] contains NaN or negative "
                             f"request times")
    out = np.full((len(mats), m0, max_apps, r0), np.inf)
    for i, a in enumerate(mats):
        out[i, :, :a.shape[1], :] = a
    return out


def _pad_rows(arr, pad: int):
    """Append ``pad`` copies of row 0 along axis 0 (the masked dummy
    problems of the mesh path, DESIGN.md §12 — every vmap lane is
    independent and the dummies' results are sliced away, so replicating
    any real row is parity-safe)."""
    if isinstance(arr, np.ndarray):
        return np.concatenate(
            [arr, np.broadcast_to(arr[:1], (pad,) + arr.shape[1:])], axis=0)
    return jnp.concatenate(
        [arr, jnp.broadcast_to(arr[:1], (pad,) + arr.shape[1:])], axis=0)


def run_pso_ga_batch(problems: Sequence[ProblemLike],
                     cfg: PSOGAConfig = PSOGAConfig(),
                     seed: Union[int, Sequence[int]] = 0,
                     bucket: bool = True,
                     return_state: bool = False,
                     incumbent: Optional[Sequence[np.ndarray]] = None,
                     migration_weight: Union[float,
                                             Sequence[float]] = 0.0,
                     warm_rescue: Optional[Sequence[bool]] = None,
                     arrivals: Optional[Sequence[np.ndarray]] = None,
                     mesh=None,
                     telemetry=None):
    """Solve N offloading problems with one fleet of swarms per bucket.

    Args:
      problems: ``SimProblem``s or ``(LayerDAG, Environment)`` pairs.
      cfg: shared PSO-GA hyperparameters (one compiled program per cfg
        per shape bucket).
      seed: one seed for every problem, or a per-problem sequence —
        problem i behaves exactly like ``run_pso_ga(..., seed=seed_i)``.
      bucket: group problems into power-of-two ``(max_p, max_S)`` shape
        buckets (``pack_fleet``, DESIGN.md §12) so a mostly-small fleet
        never pads to its largest member and repeated fleet shapes reuse
        compiled runners. ``False`` solves the whole fleet as ONE bucket
        at the exact global max (the A/B baseline).
      return_state: also return the final stacked ``_SwarmState`` in
        ORIGINAL problem order, re-assembled across buckets at the
        fleet's largest bucket ``max_p`` (genes beyond a problem's own
        bucket stay 0 — tests use it to assert padded genes were never
        touched).
      incumbent: per-problem (p_i,) incumbent assignments (online
        re-planning, DESIGN.md §9): swarms are warm-started in the
        incumbent's neighborhood (``init_swarm`` incumbent mode) and the
        fitness pays ``migration_weight`` × the Eq. 6 input-dataset cost
        for every moved layer. ``None`` is a cold solve — bit-identical
        to the pre-warm-start solver, via the SAME compiled runner. A
        per-problem entry of ``None`` demotes only that problem to a
        cold solve (stale-plan guard, DESIGN.md §11): its swarm draws
        the cold init and its migration weight is zeroed, while the
        rest of the fleet stays warm. Incumbents route with their
        problem through re-bucketing — warm state survives any fleet
        composition change that keeps the problem's own shape.
      migration_weight: scalar or per-problem migration-cost weights
        (ignored without ``incumbent``).
      warm_rescue: per-problem flags (with ``incumbent`` only): seed the
        cold tier anchors into that problem's warm swarm tail — the
        re-planner sets it where drift stranded the incumbent
        infeasible, so feasibility recovery starts from the same escape
        hatches a cold solve gets (``init_swarm`` rescue mode).
      arrivals: per-problem ``(M, n_apps_i, R)`` Monte-Carlo request
        timestamps (DESIGN.md §10) — switches every problem's fitness
        to the queue-aware traffic key under ``cfg.miss_budget``. The
        packed arrays are traced runner inputs, so sweeping the load
        (or re-planning under a load surge) never retraces.
      mesh: a ``jax.sharding.Mesh`` (``launch.mesh``) — shard each
        bucket's problem axis across the mesh's non-"model" axes via
        ``shard_map``; each bucket's N is padded to a multiple of the
        data-shard count with masked dummy problems whose results are
        discarded. Gene-for-gene identical to the single-device solve
        (DESIGN.md §12). ``None`` keeps today's single-device path.
      telemetry: a ``Telemetry`` channel (DESIGN.md §13) — the call is
        a ``fleet_batch`` span holding ``fleet_pack``, then per bucket
        ``swarm_init`` (arg ``groups``, also summed by the
        ``swarm_init.groups`` counter: the init programs it ran, one
        per exact ``(p, S)``), ``fleet_inputs``, ``fleet_solve`` (the
        runner dispatch), ``resimulate`` (ending in
        ``block_until_ready``) and
        ``unpack``, and ``gather_state`` when ``return_state``. ``None``
        falls back to the process-global channel (``set_telemetry``);
        with neither, the solve path is bit-identical to pre-telemetry
        behavior.

    Returns a list of per-problem ``PSOGAResult`` in INPUT ORDER (and
    the re-assembled state if asked) — bucket assignment is invisible in
    the output. ``record_history`` is not supported in fleet mode — use
    the sequential solver to trace a single problem's convergence curve.
    ``best_fitness`` is the migration-adjusted key when warm (the
    traffic key when ``arrivals`` is given); ``best_cost`` is always
    the raw zero-load replayed plan cost.
    """
    tel = telemetry if telemetry is not None else get_telemetry()
    with maybe_span(tel, "fleet_batch", n=len(problems)) as span_args:
        with maybe_span(tel, "fleet_pack"):
            probs = _as_problems(problems)
            n = len(probs)
            seeds = _normalize_seeds(seed, n)
            if incumbent is not None and len(incumbent) != n:
                raise ValueError(
                    f"{len(incumbent)} incumbents for {n} problems")
            if arrivals is not None and len(arrivals) != n:
                raise ValueError(
                    f"{len(arrivals)} arrival sets for {n} problems")
            mig_arr = np.broadcast_to(
                np.asarray(migration_weight, np.float32), (n,))
            fleet = pack_fleet(probs, bucket=bucket)
        if span_args is not None:
            span_args["buckets"] = len(fleet.buckets)
        traffic = arrivals is not None
        shards = 1
        if mesh is not None:
            from ..launch.mesh import data_shard_count
            shards = data_shard_count(mesh)

        results: List[Optional[PSOGAResult]] = [None] * n
        bucket_states: List[Tuple[FleetBucket, _SwarmState]] = []
        for b in fleet.buckets:
            nb = int(b.idx.shape[0])
            name = f"{b.max_p}x{b.max_S}"
            with maybe_span(tel, "swarm_init", bucket=name,
                            n=nb) as init_args:
                keys_a, X0b, incb, migb, n_groups = _init_bucket(
                    b, probs, seeds, cfg, incumbent, mig_arr, warm_rescue)
                if tel is not None:
                    init_args["groups"] = n_groups
                    tel.inc("swarm_init.groups", n_groups)
            with maybe_span(tel, "fleet_inputs", bucket=name, n=nb):
                arrb = None
                if traffic:
                    arrb = pack_arrivals([arrivals[i] for i in b.idx],
                                         fleet.max_apps)
                ppb = b.ppb
                pad = (-nb) % shards
                if pad:
                    ppb = jax.tree.map(lambda a: _pad_rows(a, pad), ppb)
                    keys_a = _pad_rows(keys_a, pad)
                    X0b = _pad_rows(X0b, pad)
                    incb = _pad_rows(incb, pad)
                    migb = _pad_rows(migb, pad)
                    if arrb is not None:
                        arrb = _pad_rows(arrb, pad)
                runner = _fleet_runner(cfg, traffic=traffic,
                                       shape_bucket=(b.max_p, b.max_S),
                                       mesh=mesh, telemetry=tel)
                args = (ppb, jnp.asarray(keys_a), jnp.asarray(X0b),
                        jnp.asarray(incb), jnp.asarray(migb))
                if traffic:
                    args = args + (jnp.asarray(arrb),)
            with maybe_span(tel, "fleet_solve", bucket=name, n=nb,
                            traffic=traffic, sharded=mesh is not None):
                state = runner(*args)
                jax.block_until_ready(state.gbest_f)

            # Re-simulate each gbest (same as the sequential epilogue).
            with maybe_span(tel, "resimulate", bucket=name, n=nb):
                if pad:
                    state = jax.tree.map(lambda a: a[:nb], state)
                res = jax.vmap(
                    lambda pp, x: simulate_padded(pp, x, cfg.faithful_sim))(
                        b.ppb, state.gbest_x)
                if tel is not None:
                    jax.block_until_ready(res)
            with maybe_span(tel, "unpack", bucket=name, n=nb):
                for j, i in enumerate(b.idx):
                    pr = probs[i]
                    feasible = bool(res.feasible[j])
                    results[i] = PSOGAResult(
                        best_x=np.asarray(state.gbest_x[j])[:pr.num_layers],
                        best_fitness=float(state.gbest_f[j]),
                        best_cost=float(res.total_cost[j]) if feasible
                        else float("inf"),
                        feasible=feasible,
                        iterations=int(state.it[j]),
                        history=None)
            bucket_states.append((b, state))

        if not return_state:
            return results
        with maybe_span(tel, "gather_state"):
            return results, _gather_state(bucket_states, n, cfg)


#: traces of the grouped init program (python side effect: trace-time
#: only, like ``_CACHE_STATS["traces"]``) — a repeat fleet, or one whose
#: group sizes round to the same power of two, must not add one.
_INIT_STATS = {"traces": 0}

_KEY_WORD = 0xFFFFFFFF


def _root_keys(seeds: Sequence[int]) -> np.ndarray:
    """``(n, 2)`` uint32: ``jax.random.PRNGKey(seed)`` of every seed,
    built on the host. Without x64, JAX keeps the seed's low 32 bits
    (the high word is 0); with it, the seed's two's-complement words."""
    x64 = bool(jax.config.jax_enable_x64)
    return np.array([[(s >> 32) & _KEY_WORD if x64 else 0, s & _KEY_WORD]
                     for s in seeds], np.uint32)


@partial(jax.jit, static_argnames="cfg")
def _init_group(roots, home, allowed, pinned, by_power, inc, has_inc,
                rescue, cfg: PSOGAConfig):
    """``(keys, X0)`` of a group of same-shape problems: each problem's
    root key split as ``run_pso_ga`` splits it, and ``init_swarm``'s
    core vmapped over the group."""
    _INIT_STATS["traces"] += 1
    def one(root, *args):
        key, k_init = jax.random.split(root)
        return key, _init_core(k_init, *args, cfg=cfg)
    return jax.vmap(one)(roots, home, allowed, pinned, by_power, inc,
                         has_inc, rescue)


def _init_bucket(b: FleetBucket, probs: Sequence[SimProblem],
                 seeds: Sequence[int], cfg: PSOGAConfig,
                 incumbent: Optional[Sequence[np.ndarray]],
                 mig_arr: np.ndarray,
                 warm_rescue: Optional[Sequence[bool]]
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray,
                            int]:
    """One bucket's solver inputs ``(keys, X0b, incb, migb)`` and the
    number of init programs it ran.

    Per-problem init mirrors run_pso_ga exactly: split the problem's own
    key, draw the link-aware swarm at the TRUE (p, S) shape, then embed
    into the bucket's padded gene space (padded genes start — and stay —
    0). Seeds, incumbents and rescue flags all route by ORIGINAL index,
    so bucket assignment never reshuffles a problem's inputs.

    The draws keep the true shape, so the members are grouped by their
    exact ``(p, S)`` and each group runs ONE jitted vmap of
    ``init_swarm``'s core (DESIGN.md §12). A group's count is padded to
    a power of two with copies of its first member (dropped after), so
    a round with another fleet mix or demotion pattern reuses the
    compiled program; each group is read back once."""
    nb = int(b.idx.shape[0])
    X0b = np.zeros((nb, cfg.pop_size, b.max_p), np.int32)
    incb = np.zeros((nb, b.max_p), np.int32)
    migb = np.zeros((nb,), np.float32)
    has_inc = np.zeros((nb,), bool)
    rescue = np.zeros((nb,), bool)
    groups: Dict[Tuple[int, int], List[int]] = {}
    for j, i in enumerate(b.idx):
        pr = probs[i]
        groups.setdefault((pr.num_layers, pr.num_servers), []).append(j)
        if incumbent is not None and incumbent[i] is not None:
            inc_i = np.asarray(incumbent[i], np.int32)
            if inc_i.shape != (pr.num_layers,):
                raise ValueError(
                    f"incumbent[{i}] has shape {inc_i.shape}, "
                    f"expected ({pr.num_layers},)")
            incb[j, :pr.num_layers] = inc_i
            migb[j] = mig_arr[i]
            has_inc[j] = True
            rescue[j] = bool(warm_rescue[i]) if warm_rescue is not None \
                else False
        # else: a demoted problem (stale incumbent, DESIGN.md §11)
        # solves cold inside the warm fleet: zero migration weight
        # multiplies the term away bit-exactly, and its init takes the
        # cold rows — identical to a cold solve of problem i.
    keys_a = _root_keys([seeds[i] for i in b.idx])
    outs = []
    for (p, _), js in groups.items():
        host = [np.stack(a) for a in
                zip(*(_init_arrays(probs[b.idx[j]]) for j in js))]
        args = [keys_a[js], *host, incb[js, :p], has_inc[js], rescue[js]]
        pad = bucket_size(len(js), floor=1) - len(js)
        if pad:
            args = [_pad_rows(a, pad) for a in args]
        outs.append(_init_group(*args, cfg=cfg))
    for ((p, _), js), (keys, X0) in zip(groups.items(),
                                         jax.device_get(outs)):
        keys_a[js] = keys[:len(js)]
        X0b[js, :, :p] = X0[:len(js)]
    return keys_a, X0b, incb, migb, len(groups)


def _gather_state(bucket_states: Sequence[Tuple[FleetBucket, _SwarmState]],
                  n: int, cfg: PSOGAConfig) -> _SwarmState:
    """Re-assemble one fleet-ordered state across buckets at the largest
    bucket's max_p: genes beyond a problem's own bucket shape are 0 —
    the same "padded genes untouched" invariant the single-bucket state
    had (tests/test_batch.py::test_padding_never_selected)."""
    gmax_p = max(b.max_p for b, _ in bucket_states)
    st0 = bucket_states[0][1]
    key_g = np.zeros((n,) + st0.key.shape[1:], np.asarray(st0.key).dtype)
    X_g = np.zeros((n, cfg.pop_size, gmax_p), np.int32)
    pbx_g = np.zeros((n, cfg.pop_size, gmax_p), np.int32)
    pbf_g = np.zeros((n, cfg.pop_size), np.asarray(st0.pbest_f).dtype)
    gbx_g = np.zeros((n, gmax_p), np.int32)
    gbf_g = np.zeros((n,), np.asarray(st0.gbest_f).dtype)
    it_g = np.zeros((n,), np.int32)
    stall_g = np.zeros((n,), np.int32)
    for b, st in bucket_states:
        key_g[b.idx] = np.asarray(st.key)
        X_g[b.idx, :, :b.max_p] = np.asarray(st.X)
        pbx_g[b.idx, :, :b.max_p] = np.asarray(st.pbest_x)
        pbf_g[b.idx] = np.asarray(st.pbest_f)
        gbx_g[b.idx, :b.max_p] = np.asarray(st.gbest_x)
        gbf_g[b.idx] = np.asarray(st.gbest_f)
        it_g[b.idx] = np.asarray(st.it)
        stall_g[b.idx] = np.asarray(st.stall)
    return _SwarmState(
        key=jnp.asarray(key_g), X=jnp.asarray(X_g),
        pbest_x=jnp.asarray(pbx_g), pbest_f=jnp.asarray(pbf_g),
        gbest_x=jnp.asarray(gbx_g), gbest_f=jnp.asarray(gbf_g),
        it=jnp.asarray(it_g), stall=jnp.asarray(stall_g))
