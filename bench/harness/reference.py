"""Plain references that the benchmark judges the planner's answers by.

Nothing here imports the program. Each function restates one documented
semantics of the planner in straightforward numpy and Python floats:

- ``replay``: Algorithm 2's schedule replay of one plan and its Eq. 8
  cost (rental + transmission), the semantics of the program's
  ``simulate_np``;
- ``traffic_replay``: the queue-aware discrete-event replay of a request
  stream (per-server FCFS in request-arrival order, same-app ties by
  request slot, cross-app ties by topological position), the numpy
  oracle of the program's traffic tests;
- ``sample_arrivals`` and ``drift_events``: the request and drift
  generators, so that the benchmark knows the draws a seed stands for.

Every arithmetic result passes through ``rnd``. ``float`` keeps float64;
``to_bfloat16`` rounds to bfloat16, the precision below the planner's
float32, and makes the control that a sound comparison has to reject.
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import Callable, Dict, List, NamedTuple

import ml_dtypes
import numpy as np

#: MB/s that stands in for a missing link (a transfer over it takes 1e9 s)
MIN_BW = 1e-9
_ENTROPY_MASK = 0xFFFF_FFFF_FFFF_FFFF
CLOUD, EDGE, DEVICE = 0, 1, 2

Rounding = Callable[[float], float]


def to_bfloat16(v: float) -> float:
    return float(ml_dtypes.bfloat16(v))


# ---------------------------------------------------------------------------
# problems
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Environment:
    """Servers and links as the reference sees them."""
    power: np.ndarray          # (S,) work units per second
    cost_per_sec: np.ndarray   # (S,) USD per second while on
    tier: np.ndarray           # (S,) 0 cloud, 1 edge, 2 device
    bandwidth: np.ndarray      # (S, S) MB/s, 0 = no link, inf on the diagonal
    tran_cost: np.ndarray      # (S, S) USD per MB, 0 on the diagonal


def environment_from_config(env: dict) -> Environment:
    """The paper's tiered environment from a configuration's tables."""
    tier = np.asarray(env["tier"], np.int32)
    bw_t = np.asarray(env["tier_bandwidth_mb_s"], float)
    tc_t = np.asarray(env["tier_transfer_usd_per_gb"], float) / 1024.0
    bw = bw_t[tier[:, None], tier[None, :]].copy()
    tc = tc_t[tier[:, None], tier[None, :]].copy()
    if "device_edge_links" in env:
        linked = {tuple(map(int, pair)) for pair in env["device_edge_links"]}
        for d in np.nonzero(tier == DEVICE)[0]:
            for e in np.nonzero(tier == EDGE)[0]:
                if (int(d), int(e)) not in linked:
                    bw[d, e] = bw[e, d] = 0.0
    np.fill_diagonal(bw, np.inf)
    np.fill_diagonal(tc, 0.0)
    return Environment(
        power=np.asarray(env["power_cpus"], float),
        cost_per_sec=np.asarray(env["cost_usd_per_hour"], float) / 3600.0,
        tier=tier, bandwidth=bw, tran_cost=tc)


class Problem:
    """One offloading problem: a layer DAG (one or more apps) placed
    over an environment. Derives its own topological order (Kahn,
    smallest index first) and parent/child lists in edge order."""

    def __init__(self, compute, edges, edge_mb, app_id, deadline, pinned,
                 env: Environment):
        self.compute = np.asarray(compute, float)
        self.edges = np.asarray(edges, np.int64).reshape(-1, 2)
        self.edge_mb = np.asarray(edge_mb, float)
        self.app_id = np.asarray(app_id, np.int64)
        self.deadline = np.atleast_1d(np.asarray(deadline, float))
        self.pinned = np.asarray(pinned, np.int64)
        self.env = env
        p = self.num_layers
        self.parents: List[List[tuple]] = [[] for _ in range(p)]
        self.children: List[List[tuple]] = [[] for _ in range(p)]
        for (u, v), mb in zip(self.edges, self.edge_mb):
            self.parents[int(v)].append((int(u), float(mb)))
            self.children[int(u)].append((int(v), float(mb)))
        indeg = np.zeros(p, np.int64)
        np.add.at(indeg, self.edges[:, 1], 1)
        ready = [j for j in range(p) if indeg[j] == 0]
        heapq.heapify(ready)
        order = []
        while ready:
            j = heapq.heappop(ready)
            order.append(j)
            for c, _ in self.children[j]:
                indeg[c] -= 1
                if indeg[c] == 0:
                    heapq.heappush(ready, c)
        if len(order) != p:
            raise ValueError("layer graph has a cycle")
        self.order = order
        bw = np.where(env.bandwidth <= 0.0, MIN_BW, env.bandwidth)
        self.inv_bw = 1.0 / bw
        self.link_ok = env.bandwidth > 0.0

    @property
    def num_layers(self) -> int:
        return int(self.compute.shape[0])

    @property
    def num_servers(self) -> int:
        return int(self.env.power.shape[0])

    @property
    def num_apps(self) -> int:
        return int(self.deadline.shape[0])


def plan_valid(prob: Problem, plan) -> bool:
    """A plan of the right shape, with genes in range, its pins honoured
    and every DAG edge on a live link (or within one server)."""
    x = np.asarray(plan)
    if x.ndim != 1 or x.shape[0] != prob.num_layers:
        return False
    if not np.issubdtype(x.dtype, np.integer):
        return False
    if np.any(x < 0) or np.any(x >= prob.num_servers):
        return False
    if np.any((prob.pinned >= 0) & (x != prob.pinned)):
        return False
    for u, v in prob.edges:
        a, b = x[u], x[v]
        if a != b and not prob.link_ok[a, b]:
            return False
    return True


# ---------------------------------------------------------------------------
# Algorithm 2 and Eq. 8
# ---------------------------------------------------------------------------

class Replay(NamedTuple):
    app_completion: np.ndarray   # (n_apps,) completion time of each app
    cost: float                  # Eq. 8: rental + transmission, USD
    static_ok: bool              # pins honoured, every edge on a live link

    def feasible(self, prob: Problem, deadline_scale: float = 1.0) -> bool:
        return bool(self.static_ok and np.all(
            self.app_completion <= prob.deadline * deadline_scale))


def replay(prob: Problem, x, faithful: bool = True,
           rnd: Rounding = float) -> Replay:
    """Replay one plan layer by layer in topological order.

    ``faithful`` follows Algorithm 2 line for line: a layer starts when
    its server's lease ends plus the longest inbound transfer, and the
    lease grows by execution and outbound transfer time. Otherwise a
    layer waits for its parents' data to arrive."""
    x = np.asarray(x, np.int64)
    s = prob.num_servers
    power, cps = prob.env.power, prob.env.cost_per_sec
    tc = prob.env.tran_cost
    lease = [0.0] * s
    t_on = [np.inf] * s
    end = [0.0] * prob.num_layers
    trans = 0.0
    link_bad = False
    for j in prob.order:
        srv = int(x[j])
        exe = rnd(prob.compute[j] / power[srv])
        max_tr, gate = 0.0, 0.0
        for pj, mb in prob.parents[j]:
            src = int(x[pj])
            t = rnd(mb * prob.inv_bw[src, srv])
            if src != srv and not prob.link_ok[src, srv]:
                link_bad = True
            max_tr = max(max_tr, t)
            gate = max(gate, rnd(end[pj] + t))
            trans = rnd(trans + rnd(tc[src, srv] * mb))
        if faithful:
            start = rnd(lease[srv] + max_tr)
        else:
            start = max(lease[srv], gate)
        t_end = rnd(start + exe)
        end[j] = t_end
        t_on[srv] = min(t_on[srv], start)
        out = 0.0
        for cj, mb in prob.children[j]:
            out = rnd(out + rnd(mb * prob.inv_bw[srv, int(x[cj])]))
        if faithful:
            lease[srv] = rnd(rnd(lease[srv] + exe) + out)
        else:
            lease[srv] = rnd(t_end + out)
    comp = 0.0
    for k in range(s):
        if np.isfinite(t_on[k]):
            comp = rnd(comp + rnd(cps[k] * rnd(lease[k] - t_on[k])))
    app_completion = np.zeros(prob.num_apps)
    np.maximum.at(app_completion, prob.app_id, np.asarray(end))
    pin_ok = bool(np.all((prob.pinned < 0) | (x == prob.pinned)))
    return Replay(app_completion=app_completion, cost=rnd(comp + trans),
                  static_ok=pin_ok and not link_bad)


class TrafficReplay(NamedTuple):
    latency: np.ndarray   # (n_apps, R); nan where no request arrived
    cost: float           # rental + transmission of the whole stream, USD
    static_ok: bool

    def misses(self, prob: Problem, deadline_scale: float = 1.0) -> int:
        lim = prob.deadline[:, None] * deadline_scale
        lat = np.where(np.isnan(self.latency), -np.inf, self.latency)
        return int(np.sum(lat > lim))

    @property
    def requests(self) -> int:
        return int(np.sum(~np.isnan(self.latency)))


def traffic_replay(prob: Problem, x, arrivals, faithful: bool = True,
                   rnd: Rounding = float) -> TrafficReplay:
    """Serve every request of ``arrivals`` ((n_apps, R) start times,
    +inf for none) through the plan: each layer of each request queues
    FCFS on its server in (arrival, request slot, topological position)
    order."""
    x = np.asarray(x, np.int64)
    arr = np.asarray(arrivals, float)
    s = prob.num_servers
    power, cps = prob.env.power, prob.env.cost_per_sec
    tc = prob.env.tran_cost
    n_apps, R = arr.shape
    steps = []
    for r in range(R):
        for t, j in enumerate(prob.order):
            a = arr[prob.app_id[j], r]
            if np.isfinite(a):
                steps.append((float(a), r, t, int(j)))
    steps.sort(key=lambda z: (z[0], z[1], z[2]))
    lease = [0.0] * s
    t_on = [np.inf] * s
    end: Dict[tuple, float] = {}
    trans = 0.0
    for a, r, _, j in steps:
        srv = int(x[j])
        exe = rnd(prob.compute[j] / power[srv])
        max_tr, gate = 0.0, a
        for pj, mb in prob.parents[j]:
            src = int(x[pj])
            t = rnd(mb * prob.inv_bw[src, srv])
            max_tr = max(max_tr, t)
            gate = max(gate, rnd(end[(r, pj)] + t))
            trans = rnd(trans + rnd(tc[src, srv] * mb))
        out = 0.0
        for cj, mb in prob.children[j]:
            out = rnd(out + rnd(mb * prob.inv_bw[srv, int(x[cj])]))
        if faithful:
            base = max(lease[srv], a)
            start = rnd(base + max_tr)
            lease[srv] = rnd(rnd(base + exe) + out)
        else:
            start = max(lease[srv], gate)
            lease[srv] = rnd(rnd(start + exe) + out)
        end[(r, j)] = rnd(start + exe)
        t_on[srv] = min(t_on[srv], start)
    comp = 0.0
    for k in range(s):
        if np.isfinite(t_on[k]):
            comp = rnd(comp + rnd(cps[k] * rnd(lease[k] - t_on[k])))
    latency = np.full((n_apps, R), np.nan)
    last: Dict[tuple, float] = {}
    for (r, j), e in end.items():
        key = (int(prob.app_id[j]), r)
        last[key] = max(last.get(key, 0.0), e)
    for i in range(n_apps):
        for r in range(R):
            if np.isfinite(arr[i, r]):
                latency[i, r] = rnd(last.get((i, r), 0.0) - arr[i, r])
    static_ok = plan_valid(prob, x)
    return TrafficReplay(latency=latency, cost=rnd(comp + trans),
                         static_ok=static_ok)


class TrafficVerdict(NamedTuple):
    """The traffic fitness key's verdict on one plan over Monte-Carlo
    draws: feasible when statically valid and the 95th percentile of the
    draws' miss rates is within the budget; cost is the draws' mean."""
    cost: float
    feasible_strict: bool   # deadlines scaled by 1 - band
    feasible_loose: bool    # deadlines scaled by 1 + band


def traffic_verdict(prob: Problem, x, draws: np.ndarray, budget: float,
                    band: float, faithful: bool = True,
                    rnd: Rounding = float) -> TrafficVerdict:
    reps = [traffic_replay(prob, x, d, faithful, rnd) for d in draws]
    n_req = [max(int(np.isfinite(d).sum()), 1) for d in draws]

    def ok(scale: float) -> bool:
        rates = [rp.misses(prob, scale) / n for rp, n in zip(reps, n_req)]
        return bool(reps[0].static_ok
                    and np.percentile(rates, 95.0) <= budget)

    return TrafficVerdict(cost=float(np.mean([rp.cost for rp in reps])),
                          feasible_strict=ok(1.0 - band),
                          feasible_loose=ok(1.0 + band))


# ---------------------------------------------------------------------------
# generators
# ---------------------------------------------------------------------------

ARRIVAL_KINDS = ("poisson", "diurnal", "bursty", "flash-crowd")
DRIFT_KINDS = ("wifi-fade", "congestion", "spot-price", "node-loss",
               "load-surge")


def _entropy(seed: int) -> int:
    return int(seed) & _ENTROPY_MASK


def _draw_poisson(rng, rate: float, horizon: float) -> List[float]:
    out: List[float] = []
    t = float(rng.exponential(1.0 / rate))
    while t < horizon:
        out.append(t)
        t += float(rng.exponential(1.0 / rate))
    return out


def _draw_thinned(rng, lam, lam_max: float, horizon: float) -> List[float]:
    out: List[float] = []
    t = float(rng.exponential(1.0 / lam_max))
    while t < horizon:
        if rng.uniform() * lam_max <= lam(t):
            out.append(t)
        t += float(rng.exponential(1.0 / lam_max))
    return out


def _mmpp_intervals(rng, horizon: float) -> List[tuple]:
    out = []
    t, high = 0.0, False
    while t < horizon:
        dwell = float(rng.exponential(horizon / (8.0 if high else 4.0)))
        out.append((t, min(t + dwell, horizon), high))
        t += dwell
        high = not high
    return out


def sample_arrivals(kind: str, n_apps: int, rate: float, horizon: float,
                    max_requests: int, n_seeds: int, seed: int
                    ) -> np.ndarray:
    """(n_seeds, n_apps, max_requests) request start times, +inf for
    none. Draw ``s`` comes from ``default_rng([seed, s])``; the mean
    intensity is about ``rate`` requests per second and app."""
    if kind not in ARRIVAL_KINDS:
        raise ValueError(f"unknown arrival kind {kind!r}")
    t = np.full((n_seeds, n_apps, max_requests), np.inf)
    for s in range(n_seeds):
        rng = np.random.default_rng([_entropy(seed), s])
        if kind == "bursty":
            ivals = _mmpp_intervals(rng, horizon)

            def lam(x: float) -> float:
                for lo, hi, high in ivals:
                    if lo <= x < hi:
                        return (2.4 if high else 0.3) * rate
                return 0.3 * rate
            lam_max = 2.4 * rate
        elif kind == "flash-crowd":
            t0 = float(rng.uniform(0.2, 0.6)) * horizon
            w = 0.15 * horizon

            def lam(x: float) -> float:
                return 0.5 * rate + (4.0 * rate if t0 <= x < t0 + w
                                     else 0.0)
            lam_max = 4.5 * rate
        elif kind == "diurnal":
            def lam(x: float) -> float:
                return rate * (1.0 + 0.9 * np.sin(
                    2.0 * np.pi * x / horizon - np.pi / 2.0))
            lam_max = 1.9 * rate
        else:
            lam, lam_max = None, rate
        for a in range(n_apps):
            if kind == "poisson":
                times = _draw_poisson(rng, rate, horizon)
            else:
                times = _draw_thinned(rng, lam, lam_max, horizon)
            times = times[:max_requests]
            t[s, a, :len(times)] = times
    return t


class DriftEvent(NamedTuple):
    """One epoch of drift, as scales on the base environment."""
    label: str
    bw_scale: np.ndarray      # (S, S)
    power_scale: np.ndarray   # (S,)
    price_scale: np.ndarray   # (S,)
    down: np.ndarray          # (S,) bool, server churned out
    load_scale: float         # on the request rate


def _identity(s: int, label: str) -> DriftEvent:
    return DriftEvent(label, np.ones((s, s)), np.ones(s), np.ones(s),
                      np.zeros(s, bool), 1.0)


def _tier_pairs(tier: np.ndarray, ta: int, tb: int) -> np.ndarray:
    a, b = tier == ta, tier == tb
    return (a[:, None] & b[None, :]) | (b[:, None] & a[None, :])


def drift_events(kind: str, tier: np.ndarray, rounds: int, seed: int,
                 severity: float) -> List[DriftEvent]:
    """``rounds`` epochs of one drift family; epoch 0 is the base
    environment. All draws come from ``default_rng(seed)``."""
    if kind not in DRIFT_KINDS:
        raise ValueError(f"unknown drift kind {kind!r}")
    tier = np.asarray(tier)
    s = tier.shape[0]
    rng = np.random.default_rng(_entropy(seed))
    events = [_identity(s, f"{kind}[base]")]
    lo = 1.0 - severity
    fade = 1.0
    for _ in range(1, rounds):
        ev = _identity(s, kind)
        if kind == "wifi-fade":
            fade = float(np.clip(fade + rng.uniform(-0.5, 0.35) * severity,
                                 lo, 1.0))
            bw = np.ones((s, s))
            bw[_tier_pairs(tier, DEVICE, EDGE)] = fade
            ev = ev._replace(label=f"wifi-fade[{fade:.2f}]", bw_scale=bw)
        elif kind == "congestion":
            cong = float(rng.uniform(lo, 1.0))
            m = (_tier_pairs(tier, CLOUD, CLOUD)
                 | _tier_pairs(tier, CLOUD, EDGE)
                 | _tier_pairs(tier, CLOUD, DEVICE))
            bw = np.ones((s, s))
            bw[m] = cong
            ev = ev._replace(label=f"congestion[{cong:.2f}]", bw_scale=bw)
        elif kind == "spot-price":
            spot = float(rng.uniform(1.0 - severity / 2, 1.0 + severity))
            price = np.ones(s)
            price[tier == CLOUD] = spot
            ev = ev._replace(label=f"spot-price[{spot:.2f}]",
                             price_scale=price)
        elif kind == "load-surge":
            surge = float(rng.uniform(1.0, 1.0 + 7.0 * severity))
            ev = ev._replace(label=f"load-surge[{surge:.1f}x]",
                             load_scale=surge)
        else:
            victim = int(rng.choice(np.nonzero(tier != DEVICE)[0]))
            down = np.zeros(s, bool)
            down[victim] = True
            ev = ev._replace(label=f"node-loss[s{victim}]", down=down)
        events.append(ev)
    return events


def env_at(base: Environment, ev: DriftEvent) -> Environment:
    """The environment of one drift epoch."""
    bw = base.bandwidth * ev.bw_scale
    if ev.down.any():
        off = ~np.eye(base.power.shape[0], dtype=bool)
        dead = ev.down[:, None] | ev.down[None, :]
        bw = np.where(dead & off, 0.0, bw)
    return Environment(power=np.maximum(base.power * ev.power_scale, 1e-12),
                       cost_per_sec=base.cost_per_sec * ev.price_scale,
                       tier=base.tier, bandwidth=bw,
                       tran_cost=base.tran_cost)


def relative_gap(got: float, want: float) -> float:
    """|got - want| / |want|; 0 when both are the same infinity."""
    if np.isinf(got) and np.isinf(want) and np.sign(got) == np.sign(want):
        return 0.0
    if not np.isfinite(got) or not np.isfinite(want):
        return float("inf")
    return abs(got - want) / max(abs(want), 1e-300)

