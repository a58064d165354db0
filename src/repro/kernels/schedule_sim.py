"""Pallas TPU kernel replaying paper Algorithm 2 for a whole particle tile.

The PSO-GA fitness hot path evaluates P particles (server-assignment
vectors) against one padded problem per swarm iteration. The scan-based
path (``core.simulator.simulate_padded`` under ``vmap``) pays per-layer
dispatch for every step of the schedule replay; this kernel moves the
layer loop *inside* one ``pallas_call`` so the whole replay of a particle
tile is a single fused program (DESIGN.md §8):

  * particles ride the LANE axis: the swarm enters transposed as
    ``X^T (max_p, P)``, so the per-layer gene reads ``x[j]`` /
    ``x[parent]`` / ``x[child]`` are dynamic single-ROW loads
    (``ref[pl.ds(j, 1), :]``), which Mosaic lowers, instead of dynamic
    lane slices, which it does not.
  * grid ``(num_particle_tiles,)`` — one grid cell replays ``tile_p``
    particles (a multiple of 128 lanes, or the whole swarm); ``jax.vmap``
    adds the fleet's problem axis as an outer grid dimension
    (``core.batch._fleet_runner`` relies on this).
  * ``lease (S, tile_p)`` / ``t_on (S, tile_p)`` and the two scalar
    accumulators (transmission cost, link violations) ride the layer
    ``fori_loop`` carry; ``end (max_p, tile_p)`` is VMEM scratch, since
    it is read and written at traced rows.
  * server-indexed lookups (``inv_bw[x[parent], x[j]]`` etc.) are one
    ``(S, S) @ (S, tile_p)`` one-hot column-select per link matrix (at
    ``Precision.HIGHEST``, which reproduces f32 operands exactly — the
    default rounds them to bf16 on the MXU) followed by a masked sublane
    sum against the other end's one-hot.
  * the per-layer DAG structure (topological order, parent/child ids
    and datasets, compute, deadlines) is shared by every particle in the
    tile, so it lives in SMEM and is read as scalars.

The kernel returns the per-particle summary the fitness key needs —
``(total_cost, feasible, Σ app_completion)`` — not the full ``SimResult``
(the solver epilogue re-simulates only the single gbest). Feasibility
folds deadlines, pins, and link violations, exactly like the scan path.

No ``repro.core`` imports here: the kernel layer stays below core
(DESIGN.md §1), so the problem arrives as raw padded arrays and the
3-case fitness key (Eq. 14–16) is applied by ``core.fitness``.

Validated in interpret mode on CPU against ``ref.schedule_replay_ref``
and the numpy oracle (``tests/test_schedule_sim.py``), compiled for a
described TPU v5e at the paper's widths (``tests/test_tpu_compile.py``),
and compared with the scan and the oracle on the chip by
``chip_smoke.py``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["schedule_replay_folded", "DEFAULT_TILE_P"]

#: particles per grid cell (the lane width); swarms of at most this many
#: particles run as one tile, larger ones are padded up to a multiple.
DEFAULT_TILE_P = 128


def tile_particles(P: int, tile_p: int) -> Tuple[int, int, int]:
    """``(tile_p, n_tiles, p_pad)`` for a swarm of ``P`` particles: a swarm
    no larger than ``tile_p`` is one tile of exactly ``P`` lanes (a block
    equal to the whole axis needs no 128-alignment on TPU)."""
    tile_p = min(tile_p, max(P, 1))
    n_tiles = pl.cdiv(P, tile_p)
    return tile_p, n_tiles, n_tiles * tile_p


def pad_lanes(a: jnp.ndarray, p_pad: int) -> jnp.ndarray:
    """Pad the last (particle) axis to ``p_pad`` with copies of particle 0."""
    P = a.shape[-1]
    if p_pad == P:
        return a
    fill = jnp.broadcast_to(a[..., :1], a.shape[:-1] + (p_pad - P,))
    return jnp.concatenate([a, fill], axis=-1)


def _select_cols(mat: jnp.ndarray, one_hot: jnp.ndarray) -> jnp.ndarray:
    """``(S, S) @ (S, T)`` one-hot -> ``(S, T)``: column ``mat[:, sel_t]``
    per lane, exact in f32."""
    return jax.lax.dot_general(mat, one_hot, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _pick(cols: jnp.ndarray, one_hot: jnp.ndarray) -> jnp.ndarray:
    """``(S, T)`` masked sublane sum -> ``(1, T)``: ``cols[sel_t, t]``."""
    return jnp.sum(cols * one_hot, axis=0, keepdims=True)


def _schedule_kernel(order_ref, compute_ref, pidx_ref, pmb_ref, cidx_ref,
                     cmb_ref, deadline_ref,
                     xt_ref, app_ref, pin_ref, power_ref, cost_ref,
                     links_ref,
                     total_ref, feas_ref, tsum_ref,
                     end_s, *,
                     tile_p: int, max_p: int, max_in: int, max_out: int,
                     max_S: int, max_apps: int, faithful: bool):
    f32 = jnp.float32
    row_S = jax.lax.broadcasted_iota(jnp.int32, (max_S, tile_p), 0)
    power = power_ref[:]                           # (S, 1)
    # links_ref stacks [inv_bw, tran_cost, link_ok, inv_bw^T, link_ok^T];
    # selecting column x[j] of each gives the incoming-edge rows
    # (indexed by the parent's server) and the outgoing-edge rows
    # (indexed by the child's server) of layer j's server.
    inv_bw, tran, link, inv_bw_t, link_t = (links_ref[k] for k in range(5))
    end_s[:] = jnp.zeros((max_p, tile_p), f32)

    def one_hot(srv):                              # (1, T) -> (S, T) f32
        return (row_S == srv).astype(f32)

    def body(t, carry):
        lease, t_on, trans_acc, bad_acc = carry
        j = order_ref[0, t]                        # scalar int32
        valid = j >= 0
        jsafe = jnp.maximum(j, 0)
        srv = xt_ref[pl.ds(jsafe, 1), :]           # (1, T) int32
        srv_oh = one_hot(srv)
        lease_srv = _pick(lease, srv_oh)
        exe = compute_ref[0, jsafe] / _pick(power, srv_oh)
        in_ibw = _select_cols(inv_bw, srv_oh)      # inv_bw[:, srv]
        in_tc = _select_cols(tran, srv_oh)         # tran_cost[:, srv]
        in_lk = _select_cols(link, srv_oh)         # link_ok[:, srv]
        out_ibw = _select_cols(inv_bw_t, srv_oh)   # inv_bw[srv, :]
        out_lk = _select_cols(link_t, srv_oh)      # link_ok[srv, :]

        zero = jnp.zeros((1, tile_p), f32)
        max_trans, gate, trans_add, bad_add = zero, zero, zero, zero
        for k in range(max_in):                    # DAG structure: scalars
            pj = pidx_ref[0, jsafe * max_in + k]
            pmask = (pj >= 0) & valid
            pjs = jnp.maximum(pj, 0)
            mb = pmb_ref[0, jsafe * max_in + k]
            psrv = xt_ref[pl.ds(pjs, 1), :]
            psrv_oh = one_hot(psrv)
            tt = mb * _pick(in_ibw, psrv_oh)
            lk = _pick(in_lk, psrv_oh)
            max_trans = jnp.maximum(max_trans, jnp.where(pmask, tt, 0.0))
            if not faithful:   # faithful recurrence never reads `end`
                ep = end_s[pl.ds(pjs, 1), :]
                gate = jnp.maximum(gate, jnp.where(pmask, ep + tt, 0.0))
            trans_add += jnp.where(pmask, mb * _pick(in_tc, psrv_oh), 0.0)
            bad_add += jnp.where(pmask & (psrv != srv), 1.0 - lk, 0.0)

        out_t = zero
        for k in range(max_out):
            cj = cidx_ref[0, jsafe * max_out + k]
            cmask = (cj >= 0) & valid
            csrv = xt_ref[pl.ds(jnp.maximum(cj, 0), 1), :]
            csrv_oh = one_hot(csrv)
            out_t += jnp.where(
                cmask,
                cmb_ref[0, jsafe * max_out + k] * _pick(out_ibw, csrv_oh),
                0.0)
            bad_add += jnp.where(cmask & (csrv != srv),
                                 1.0 - _pick(out_lk, csrv_oh), 0.0)

        if faithful:
            start = lease_srv + max_trans
            new_lease = lease_srv + exe + out_t
        else:
            start = jnp.maximum(lease_srv, gate)
            new_lease = start + exe + out_t
        t_end = start + exe
        upd = (srv_oh > 0) & valid                 # (S, T)
        lease = jnp.where(upd, new_lease, lease)
        t_on = jnp.minimum(t_on, jnp.where(upd, start, jnp.inf))
        old_end = end_s[pl.ds(jsafe, 1), :]
        end_s[pl.ds(jsafe, 1), :] = jnp.where(valid, t_end, old_end)
        return lease, t_on, trans_acc + trans_add, bad_acc + bad_add

    init = (jnp.zeros((max_S, tile_p), f32),
            jnp.full((max_S, tile_p), jnp.inf, f32),
            jnp.zeros((1, tile_p), f32), jnp.zeros((1, tile_p), f32))
    lease, t_on, trans_acc, bad_acc = jax.lax.fori_loop(0, max_p, body, init)

    end = end_s[:]                                 # (max_p, T)
    app_id = app_ref[:]                            # (max_p, 1)
    deadline_ok = jnp.ones((1, tile_p), jnp.bool_)
    tsum = jnp.zeros((1, tile_p), f32)
    for a in range(max_apps):                      # max_apps is small
        appc = jnp.maximum(jnp.max(jnp.where(app_id == a, end, -jnp.inf),
                                   axis=0, keepdims=True), 0.0)
        deadline_ok &= appc <= deadline_ref[0, a]
        tsum += appc
    pinned = pin_ref[:]                            # (max_p, 1)
    pin_hit = jnp.where((pinned < 0) | (xt_ref[:] == pinned), 1.0, 0.0)
    pin_ok = jnp.min(pin_hit, axis=0, keepdims=True) > 0.0
    used = ~jnp.isinf(t_on)
    t_on_safe = jnp.where(used, t_on, 0.0)
    comp = jnp.sum(jnp.where(used, cost_ref[:] * (lease - t_on_safe), 0.0),
                   axis=0, keepdims=True)
    total_ref[:] = comp + trans_acc
    feas_ref[:] = jnp.where(deadline_ok & pin_ok & (bad_acc == 0.0),
                            1.0, 0.0)
    tsum_ref[:] = tsum


def schedule_replay_folded(
        order: jnp.ndarray, compute: jnp.ndarray, parent_idx: jnp.ndarray,
        parent_mb: jnp.ndarray, child_idx: jnp.ndarray,
        child_mb: jnp.ndarray, app_id: jnp.ndarray, deadline: jnp.ndarray,
        pinned: jnp.ndarray, power: jnp.ndarray, cost_per_sec: jnp.ndarray,
        inv_bw: jnp.ndarray, tran_cost: jnp.ndarray, link_ok: jnp.ndarray,
        X: jnp.ndarray, *, faithful: bool = True,
        tile_p: int = DEFAULT_TILE_P, interpret: bool
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Replay Algorithm 2 for every particle in ``X``.

    Args use the padded-problem layout of ``core.simulator.PaddedProblem``
    (``order`` padded -1, parent/child ids padded -1, servers padded
    unreachable, apps padded deadline +inf); ``X`` is ``(P, max_p)``
    int32 server assignments. ``interpret`` has no default: the caller
    picks compiled (TPU) or interpreted (CPU) — ``kernels.ops.
    interpret_default`` is the platform rule. Returns per-particle
    ``(total_cost (P,) f32, feasible (P,) bool, time_sum (P,) f32)`` where
    ``time_sum`` is ``Σ_i T_i^comp`` (the Case-3 fitness input, Eq. 16).
    """
    X = jnp.asarray(X, jnp.int32)
    P, max_p = X.shape
    max_S = power.shape[0]
    max_in = parent_idx.shape[1]
    max_out = child_idx.shape[1]
    max_apps = deadline.shape[0]
    tile_p, n_tiles, p_pad = tile_particles(P, tile_p)

    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    link_f = f32(link_ok)
    links = jnp.stack([f32(inv_bw), f32(tran_cost), link_f,
                       f32(inv_bw).T, link_f.T])   # (5, S, S)
    kernel = functools.partial(
        _schedule_kernel, tile_p=tile_p, max_p=max_p, max_in=max_in,
        max_out=max_out, max_S=max_S, max_apps=max_apps, faithful=faithful)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    full = lambda shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    row = pl.BlockSpec((1, tile_p), lambda i: (0, i))
    total, feas, tsum = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[smem] * 7 + [
            pl.BlockSpec((max_p, tile_p), lambda i: (0, i)),   # X^T tile
            full((max_p, 1)),                                  # app_id
            full((max_p, 1)),                                  # pinned
            full((max_S, 1)),                                  # power
            full((max_S, 1)),                                  # cost_per_sec
            full((5, max_S, max_S)),                           # link mats
        ],
        out_specs=[row] * 3,
        out_shape=[jax.ShapeDtypeStruct((1, p_pad), jnp.float32)] * 3,
        scratch_shapes=[pltpu.VMEM((max_p, tile_p), jnp.float32)],  # end
        interpret=interpret,
        name="schedule_replay",
    )(*(a.reshape(1, -1) for a in (
        i32(order), f32(compute), i32(parent_idx), f32(parent_mb),
        i32(child_idx), f32(child_mb), f32(deadline))),
      pad_lanes(X.T, p_pad), i32(app_id)[:, None], i32(pinned)[:, None],
      f32(power)[:, None], f32(cost_per_sec)[:, None], links)
    return total[0, :P], feas[0, :P] > 0.0, tsum[0, :P]
