"""Pallas TPU kernel replaying the merged-order FCFS traffic replay for a
whole particle tile (DESIGN.md §10).

``core.traffic.simulate_traffic_swarm`` is the hot loop of every
traffic-aware solve: R request copies of the schedule × the swarm × every
PSO-GA iteration. Its scan pays per-step dispatch for all ``R·max_p``
merged steps — including every padded-layer and padded-request no-op.
This kernel is the queue-aware twin of ``kernels/schedule_sim.py``: the
merged event walk moves *inside* one ``pallas_call`` so the whole
``(P, R·max_p)`` replay is a single fused program, and the walk itself
only covers the ``n_valid`` REAL steps (see below).

  * particles ride the LANE axis, as in ``schedule_sim``: every
    per-(particle, layer) input enters transposed ``(max_p, P)`` and a
    step reads its layer's row with a dynamic single-row load.
  * grid ``(num_particle_tiles,)`` — one grid cell replays ``tile_p``
    particles; ``jax.vmap`` over Monte-Carlo arrival seeds (and over the
    fleet axis in ``core.batch._fleet_runner``) adds outer grid
    dimensions.
  * carry per tile: per-server queue tails ``lease (S, tile_p)`` and
    first-use ``t_on (S, tile_p)`` ride the walk's ``fori_loop``;
    per-(request, layer) end times
    ``end (R·max_p8, tile_p)`` are VMEM scratch (``max_p8`` = ``max_p``
    rounded up to the 8-row sublane tile, so each request's block of
    rows starts tile-aligned).
  * the merged ``(arrival, slot, topo)`` event order is precomputed on
    the host side of the call with padding COMPACTED to the tail: the
    sort key is ``arrival`` for real steps and +inf for padded-layer /
    padded-request steps, so all valid steps form a contiguous prefix
    and the kernel's ``fori_loop`` runs ``n_valid`` iterations instead
    of ``R·max_p``. Compaction is order-preserving — valid steps keep
    their exact keys and the ``(request slot, topo position)``
    tie-break, so the lease/end/t_on evolution is step-for-step the
    scan's (masked no-ops were exact identities). The order tables and
    the DAG's parent steps are SMEM scalars.
  * each step applies the arrival start-gate on-chip —
    ``max(lease[s], a_r)`` in faithful mode, ``max(lease[s], a_r,
    parent end + transfer)`` in corrected mode — and the epilogue folds
    the per-(app, request) completion latencies into the deadline-miss
    count and Σ-latency reductions the contention fitness key needs.
    Only the mode's own inputs reach the kernel: the faithful walk
    reads the per-layer max incoming transfer, the corrected walk the
    per-edge transfers ``(max_in, max_p, tile_p)``.

Static feasibility (pins honored, links legal) is arrival-independent,
so it is computed OUTSIDE the walk from ALL valid layers — a plan with
an illegal link is infeasible even for requests that never arrive. So
is transmission cost: it is summed outside the walk over the walked
steps, in the scan engine's order.

No ``repro.core`` imports here: the kernel layer stays below core
(DESIGN.md §1); the problem arrives as raw padded arrays and the
contention key (miss budget, MISS_PENALTY branch) is applied by
``core.fitness``. Validated in interpret mode on CPU against
``ref.traffic_replay_ref``, the scan engine, and the numpy DES oracle
(``tests/test_traffic_kernel.py``), compiled for a described TPU v5e
(``tests/test_tpu_compile.py``), and compared with the scan on the chip
by ``chip_smoke.py``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .schedule_sim import DEFAULT_TILE_P, pad_lanes, tile_particles

__all__ = ["traffic_replay_folded"]


def _traffic_kernel(qm_ref, slotm_ref, slot0_ref, arrm_ref, nv_ref,
                    pstep_ref, deadline_ref, rv_ref, arr2_ref,
                    srv_ref, exe_ref, ot_ref, trans_ref, app_ref, cost_ref,
                    comp_ref, miss_ref, lat_ref, latency_ref,
                    end_s, *,
                    tile_p: int, max_p8: int, max_in: int, max_S: int,
                    max_apps: int, R: int, faithful: bool):
    f32 = jnp.float32
    row_S = jax.lax.broadcasted_iota(jnp.int32, (max_S, tile_p), 0)
    end_s[:] = jnp.zeros((R * max_p8, tile_p), f32)

    def body(t, carry):
        lease, t_on = carry
        q = qm_ref[0, t]                            # topo position, scalar
        slot = slotm_ref[0, t]                      # r·max_p8 + layer id
        a_t = arrm_ref[0, t]                        # request arrival time
        srv = srv_ref[pl.ds(q, 1), :]              # (1, T)
        srv_oh = (row_S == srv).astype(f32)        # (S, T)
        lease_srv = jnp.sum(lease * srv_oh, axis=0, keepdims=True)
        exe = exe_ref[pl.ds(q, 1), :]
        ot = ot_ref[pl.ds(q, 1), :]
        if faithful:
            mt = trans_ref[pl.ds(q, 1), :]
            base = jnp.maximum(lease_srv, a_t)
            start = base + mt
            new_lease = base + exe + ot
        else:
            slot0 = slot0_ref[0, t]                 # r·max_p8
            gate = jnp.zeros((1, tile_p), f32)
            for k in range(max_in):                # DAG structure: scalars
                pj = pstep_ref[0, q * max_in + k]
                ep = end_s[pl.ds(slot0 + jnp.maximum(pj, 0), 1), :]
                ttk = trans_ref[k, pl.ds(q, 1), :]
                gate = jnp.maximum(gate, jnp.where(pj >= 0, ep + ttk, 0.0))
            gate = jnp.maximum(gate, a_t)
            start = jnp.maximum(lease_srv, gate)
            new_lease = start + exe + ot
        t_end = start + exe
        on_srv = srv_oh > 0
        lease = jnp.where(on_srv, new_lease, lease)
        t_on = jnp.minimum(t_on, jnp.where(on_srv, start, jnp.inf))
        end_s[pl.ds(slot, 1), :] = t_end
        return lease, t_on

    # only the compacted valid prefix is walked — padded-layer and
    # +inf-request steps sort past n_valid and are never touched.
    init = (jnp.zeros((max_S, tile_p), f32),
            jnp.full((max_S, tile_p), jnp.inf, f32))
    lease, t_on = jax.lax.fori_loop(0, nv_ref[0, 0], body, init)

    app_id = app_ref[:]                            # (max_p8, 1)
    miss_cnt = jnp.zeros((1, tile_p), f32)
    lat_sum = jnp.zeros((1, tile_p), f32)
    for a in range(max_apps):                      # small static loops
        sel = app_id == a
        for r in range(R):
            seg = end_s[r * max_p8:(r + 1) * max_p8, :]
            appc = jnp.max(jnp.where(sel, seg, -jnp.inf), axis=0,
                           keepdims=True)
            real = rv_ref[0, a * R + r] > 0
            latv = jnp.where(real, appc - arr2_ref[0, a * R + r], 0.0)
            latency_ref[a * R + r:a * R + r + 1, :] = latv
            miss_cnt += jnp.where(real & (latv > deadline_ref[0, a]), 1.0,
                                  0.0)
            lat_sum += latv
    used = ~jnp.isinf(t_on)
    t_on_safe = jnp.where(used, t_on, 0.0)
    comp_ref[:] = jnp.sum(
        jnp.where(used, cost_ref[:] * (lease - t_on_safe), 0.0), axis=0,
        keepdims=True)
    miss_ref[:] = miss_cnt
    lat_ref[:] = lat_sum


def traffic_replay_folded(
        order: jnp.ndarray, compute: jnp.ndarray, parent_idx: jnp.ndarray,
        parent_mb: jnp.ndarray, child_idx: jnp.ndarray,
        child_mb: jnp.ndarray, app_id: jnp.ndarray, deadline: jnp.ndarray,
        pinned: jnp.ndarray, power: jnp.ndarray, cost_per_sec: jnp.ndarray,
        inv_bw: jnp.ndarray, tran_cost: jnp.ndarray, link_ok: jnp.ndarray,
        num_apps: jnp.ndarray, X: jnp.ndarray, arr: jnp.ndarray, *,
        faithful: bool = True, tile_p: int = DEFAULT_TILE_P,
        interpret: bool
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray,
           jnp.ndarray]:
    """Queue-aware FCFS replay of one arrival draw for every particle.

    Args use the padded-problem layout of ``core.simulator.PaddedProblem``
    plus its true app count ``num_apps`` (a 0-d int32, traced per problem
    under the fleet vmap); ``X`` is ``(P, max_p)`` int32 assignments and
    ``arr`` is ``(max_apps, R)`` per-app request timestamps, +inf padded.
    ``interpret`` has no default (see ``schedule_replay_folded``).
    Returns per-particle ``(total_cost (P,), miss_rate (P,), lat_sum
    (P,), static_ok (P,) bool, latency (P, max_apps, R))`` — the summary
    ``core.fitness.make_swarm_fitness(arrivals=...)`` folds into the
    contention key, with the full latency grid kept for request-level
    differential testing.
    """
    X = jnp.asarray(X).astype(jnp.int32)
    arr = jnp.asarray(arr).astype(jnp.float32)
    P, max_p = X.shape
    max_S = power.shape[0]
    max_in = parent_idx.shape[1]
    max_apps = deadline.shape[0]
    R = arr.shape[-1]
    max_p8 = -(-max_p // 8) * 8

    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    order = i32(order)
    app_ids = i32(app_id)
    inv_bw_f = f32(inv_bw)
    link_b = jnp.asarray(link_ok).astype(bool)

    # ---- phase 1: carry-independent per-(particle, layer) quantities —
    # the kernel-layer twin of ``core.simulator._swarm_phase1`` ----
    valid = order >= 0
    jsafe = jnp.where(valid, order, 0)
    srv = jnp.take(X, jsafe, axis=1)                       # (P, max_p)
    exe = f32(compute)[jsafe][None, :] / f32(power)[srv]
    pars = i32(parent_idx)[jsafe]                          # (max_p, max_in)
    pmask = (pars >= 0) & valid[:, None]
    psafe = jnp.where(pmask, pars, 0)
    psrv = jnp.take(X, psafe, axis=1)                      # (P, max_p, max_in)
    srv_b = srv[:, :, None]
    mb = f32(parent_mb)[jsafe][None, :, :]
    tt = mb * inv_bw_f[psrv, srv_b]
    pm = pmask[None, :, :]
    max_trans = jnp.max(jnp.where(pm, tt, 0.0), axis=2, initial=0.0)
    tc = jnp.where(pm, f32(tran_cost)[psrv, srv_b] * mb, 0.0)
    link_bad = jnp.any(pm & ~link_b[psrv, srv_b] & (psrv != srv_b),
                       axis=(1, 2))
    kids = i32(child_idx)[jsafe]
    kmask = ((kids >= 0) & valid[:, None])[None, :, :]
    ksrv = jnp.take(X, jnp.where(kmask[0], kids, 0), axis=1)
    out_t = jnp.sum(jnp.where(kmask, f32(child_mb)[jsafe][None]
                              * inv_bw_f[srv_b, ksrv], 0.0), axis=2)
    link_bad = link_bad | jnp.any(
        kmask & ~link_b[srv_b, ksrv] & (ksrv != srv_b), axis=(1, 2))
    pin = i32(pinned)[None, :]
    # arrival-independent: covers ALL valid layers, walked or not
    static_ok = jnp.all((pin < 0) | (X == pin), axis=1) & ~link_bad

    # ---- merged (arrival, slot, topo) order, padding compacted ----
    # padded-layer steps take key +inf, joining +inf-request steps at
    # the tail; valid steps keep their exact keys so the stable
    # (arrival, request slot, topo position) order among them is
    # unchanged — the walk covers exactly the first n_valid entries.
    app = app_ids[jsafe]
    rep_t = jnp.tile(jnp.arange(max_p), R)
    rep_r = jnp.repeat(jnp.arange(R), max_p)
    key = jnp.where(valid[rep_t], arr[app[rep_t], rep_r], jnp.inf)
    perm = jnp.lexsort((rep_t, rep_r, key))
    q_m = rep_t[perm].astype(jnp.int32)                    # (T,)
    r_m = rep_r[perm]
    key_m = key[perm]
    valid_m = jnp.isfinite(key_m)
    nv = jnp.sum(valid_m).astype(jnp.int32)[None]          # (1,)
    slot_m = (r_m * max_p8 + jsafe[q_m]).astype(jnp.int32)
    slot0_m = (r_m * max_p8).astype(jnp.int32)
    arr_m = jnp.where(valid_m, key_m, 0.0).astype(jnp.float32)
    # transmission cost is carry-independent: charged once per walked
    # step, summed here exactly as the scan engine sums it
    trans_cost = jnp.sum(jnp.where(valid_m[None, :, None],
                                   jnp.take(tc, q_m, axis=1), 0.0),
                         axis=(1, 2))
    pstep = jnp.where(pmask, psafe, -1).astype(jnp.int32).reshape(-1)

    app_real = jnp.arange(max_apps) < num_apps
    req_valid = jnp.isfinite(arr) & app_real[:, None]      # (max_apps, R)
    rv = req_valid.astype(jnp.float32).reshape(-1)
    arr2 = jnp.where(req_valid, arr, 0.0).reshape(-1)
    n_req = jnp.maximum(jnp.sum(rv), 1.0)
    # rows max_p..max_p8 of each request block are never written: app -1
    app_col = jnp.full((max_p8, 1), -1, jnp.int32).at[:max_p, 0].set(app_ids)

    tile_p, n_tiles, p_pad = tile_particles(P, tile_p)
    lanes = lambda a: pad_lanes(f32(a).T, p_pad)           # (..., p_pad)
    if faithful:
        trans, trans_spec = lanes(max_trans), (max_p, tile_p)
    else:                                                  # (max_in, max_p, P)
        trans = pad_lanes(jnp.transpose(f32(tt), (2, 1, 0)), p_pad)
        trans_spec = (max_in, max_p, tile_p)

    kernel = functools.partial(
        _traffic_kernel, tile_p=tile_p, max_p8=max_p8, max_in=max_in,
        max_S=max_S, max_apps=max_apps, R=R, faithful=faithful)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    full = lambda shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    tile = lambda shape: pl.BlockSpec(
        shape, lambda i: (0,) * (len(shape) - 1) + (i,))
    comp, miss_cnt, lat_sum, latency = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[smem] * 9 + [
            tile((max_p, tile_p)),                                   # srv
            tile((max_p, tile_p)),                                   # exe
            tile((max_p, tile_p)),                                   # ot
            tile(trans_spec),                                        # mt / tt
            full((max_p8, 1)),                                       # app_id
            full((max_S, 1)),                                        # cost
        ],
        out_specs=[tile((1, tile_p))] * 3 + [tile((max_apps * R, tile_p))],
        out_shape=[jax.ShapeDtypeStruct((1, p_pad), jnp.float32)] * 3
        + [jax.ShapeDtypeStruct((max_apps * R, p_pad), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((R * max_p8, tile_p), jnp.float32)],
        interpret=interpret,
        name="traffic_replay",
    )(*(a.reshape(1, -1) for a in (q_m, slot_m, slot0_m, arr_m, nv, pstep,
                                    f32(deadline), rv, arr2)),
      pad_lanes(srv.T, p_pad), lanes(exe), lanes(out_t), trans, app_col,
      f32(cost_per_sec)[:, None])
    return (comp[0, :P] + trans_cost, miss_cnt[0, :P] / n_req, lat_sum[0, :P],
            static_ok, latency[:, :P].T.reshape(P, max_apps, R))
