"""The program's stage spans inside a run's window, reduced to numbers:
stage time per call, the time JAX spent compiling, and the share of the
chip's idle time that no stage span explains.

A stage is a span that does work of its own (``swarm_init``,
``resimulate``, ``jit_compile``, ...). A container only holds other
spans: the benchmark's own span around a call, and the program's spans
around a round or a whole fleet solve. A program without stage spans
(``fleet_batch`` is the outermost) gives ``None`` from every reader.
"""
from __future__ import annotations

from typing import Iterable, List, Optional, Tuple

from . import tracing

#: spans that hold other spans and name no stage of their own
CONTAINERS = frozenset({"fleet_call", "service_round", "fleet_batch",
                        "round", "solve", "replan_round", "warm_solve",
                        "cold_solve"})
#: the spans the program makes of JAX's compile events
COMPILE_SPANS = ("jit_trace", "jit_lower", "jit_compile")


def instrumented(run) -> bool:
    """Whether the program wrote its stage spans in the window."""
    return bool(run.spans_named("fleet_batch"))


def seconds(run, *names: str) -> float:
    """Summed seconds of the named spans inside the window."""
    return sum(s.t1 - s.t0 for n in names for s in run.spans_named(n))


def ms_per_problem(run, per: str, *names: str) -> Optional[float]:
    """Milliseconds of the named stages per ``per`` span (a fleet call or
    a service round) and per problem."""
    calls = run.spans_named(per)
    if not calls or not instrumented(run):
        return None
    return 1e3 * seconds(run, *names) / (len(calls) * len(run.layers))


def union_s(intervals: Iterable[Tuple[float, float]]) -> float:
    """Seconds covered by the union of the intervals."""
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def compile_ms_per(run, per: str) -> Optional[float]:
    """Milliseconds per ``per`` span in which JAX traced, lowered or
    compiled (the union of the compile spans: they nest)."""
    calls = run.spans_named(per)
    if not calls or not instrumented(run):
        return None
    spans = [(s.t0, s.t1) for n in COMPILE_SPANS for s in run.spans_named(n)]
    return 1e3 * union_s(spans) / len(calls)


def _gaps(busy: List[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi] outside the busy intervals."""
    gaps, t = [], lo
    for a, b in busy + [(hi, hi)]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    return gaps


def idle_unnamed(run) -> Optional[float]:
    """Percent of the first chip's idle time in the window (the gaps
    ``tracing.idle_gaps`` names) during which the innermost open span is
    a container or no span is open."""
    if run.trace is None or not instrumented(run):
        return None
    devs = sorted(run.trace["devices"], key=int)
    if not devs:
        return None
    lo, hi = run.window
    gaps = _gaps(tracing.busy_intervals(run.trace, devs[0], run.window),
                 lo, hi)
    idle = sum(b - a for a, b in gaps)
    if idle <= 0.0:
        return None
    spans = sorted((s for s in run.spans if s.t1 > lo and s.t0 < hi),
                   key=lambda s: s.t0)
    bounds = sorted({lo, hi}
                    | {t for g in gaps for t in g}
                    | {min(max(t, lo), hi) for s in spans
                       for t in (s.t0, s.t1)})
    unnamed, k, g = 0.0, 0, 0
    open_: list = []
    for u, v in zip(bounds, bounds[1:]):
        mid = 0.5 * (u + v)
        while g < len(gaps) and gaps[g][1] <= u:
            g += 1
        if g == len(gaps):
            break
        while k < len(spans) and spans[k].t0 <= mid:
            open_.append(spans[k])
            k += 1
        open_ = [s for s in open_ if s.t1 > mid]
        if not gaps[g][0] <= u < gaps[g][1]:
            continue
        # the latest to open; of two opened together, the shorter
        inner = max(open_, key=lambda s: (s.t0, -s.t1)) if open_ else None
        if inner is None or inner.name in CONTAINERS:
            unnamed += v - u
    return 100.0 * unnamed / idle
