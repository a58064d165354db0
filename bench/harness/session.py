"""One process's measurement: set-up phases, the window, JAX's compile
counts, host spans, and in a traced run the program's telemetry and the
device trace."""
from __future__ import annotations

import contextlib
import dataclasses
import shutil
import tempfile
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from . import tracing

_MASK64 = 0xFFFF_FFFF_FFFF_FFFF


def derive(seed: int, *parts: int, bits: int = 31) -> int:
    """A ``bits``-bit seed for one part of a run, drawn from ``seed``."""
    ss = np.random.SeedSequence([int(seed) & _MASK64]
                                + [int(p) & _MASK64 for p in parts])
    return int(ss.generate_state(1, np.uint64)[0] >> np.uint64(64 - bits))


class Span(NamedTuple):
    name: str
    t0: float    # perf_counter seconds
    t1: float
    args: dict


class Step(NamedTuple):
    """One call of the program inside the window: a fleet solve or a
    service round."""
    t0: float
    t1: float
    iterations: Tuple[int, ...]        # solver iterations per problem
    draws: Tuple[Tuple[int, ...], ...]  # requests in each solver draw,
    #                                    per problem (service only)


@dataclasses.dataclass
class Run:
    """What a run measured. The per-layer readers take their numbers
    from this record and nothing else."""
    kind: str                      # "fleet" or "service"
    device_kind: str
    layers: List[int]              # per problem
    edges: List[int]               # per problem
    servers: int
    apps: int                      # apps per problem
    pop_size: int
    max_requests: int
    steps: List[Step]
    window: Tuple[float, float]
    spans: List[Span]              # the benchmark's and the program's
    compiles_in_window: int
    trace: Optional[dict] = None   # tracing.capture's record (traced runs)

    def spans_named(self, name: str) -> List[Span]:
        lo, hi = self.window
        return [s for s in self.spans
                if s.name == name and s.t0 >= lo and s.t1 <= hi]


class Session:
    """Clock, compile counter and (with ``trace``) telemetry and profiler
    of one benchmark process."""

    def __init__(self, t_start: float, trace: bool, telemetry_cls=None):
        import jax
        self._jax = jax
        self.t_start = t_start
        self.trace = trace
        self.phases: List[Tuple[str, float]] = []
        self.compiles = {"count": 0, "seconds": 0.0, "cache_hits": 0}
        self.host_spans: List[Span] = []
        self.setup_s: Optional[float] = None
        self.window: Optional[Tuple[float, float]] = None
        self.window_compiles = 0
        self.device_trace: Optional[dict] = None
        self._compiles_at_open = 0
        self._logdir: Optional[str] = None
        self._win_ann = None
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        self.tel = None
        if trace and telemetry_cls is not None:
            self.tel = telemetry_cls()
            self._sync = time.perf_counter()
            self.tel.instant("bench_sync")

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles["count"] += 1
            self.compiles["seconds"] += secs

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.compiles["cache_hits"] += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        t = time.perf_counter()
        try:
            yield
        finally:
            self.phases.append((name, time.perf_counter() - t))

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """A host span of the benchmark's own, also written into the
        profiler's trace in a traced run."""
        ann = (self._jax.profiler.TraceAnnotation(
            tracing.ANNOTATION_PREFIX + name) if self.trace
            else contextlib.nullcontext())
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.host_spans.append(
                    Span(name, t0, time.perf_counter(), args))

    def open_window(self) -> float:
        self.setup_s = time.perf_counter() - self.t_start
        self.setup_compiles = dict(self.compiles)
        self._compiles_at_open = self.compiles["count"]
        if self.trace:
            self._logdir = tempfile.mkdtemp(prefix="bench-trace-")
            # device operations and the benchmark's own annotations only:
            # no Python function events, which would swamp the trace
            opts = self._jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            self._jax.profiler.start_trace(self._logdir,
                                           profiler_options=opts)
            self._win_ann = self._jax.profiler.TraceAnnotation(
                tracing.WINDOW_ANNOTATION)
            self._win_ann.__enter__()
        t0 = time.perf_counter()
        self.window = (t0, t0)
        return t0

    def close_window(self, t_end: float) -> None:
        self.window = (self.window[0], t_end)
        self.window_compiles = self.compiles["count"] - self._compiles_at_open
        if self.trace:
            self._win_ann.__exit__(None, None, None)
            self._jax.profiler.stop_trace()
            try:
                self.device_trace = tracing.capture(self._logdir,
                                                    self.window[0])
            finally:
                shutil.rmtree(self._logdir, ignore_errors=True)

    def program_spans(self) -> List[Span]:
        """The program's telemetry spans on the perf_counter clock."""
        if self.tel is None:
            return []
        events = self.tel.tracer.events()
        sync = next(e["ts"] for e in events if e["name"] == "bench_sync")
        at = lambda ts: self._sync + (ts - sync) * 1e-6  # noqa: E731
        open_: Dict[int, list] = {}
        out: List[Span] = []
        for e in events:
            if e["ph"] == "B":
                open_.setdefault(e["tid"], []).append(e)
            elif e["ph"] == "E" and open_.get(e["tid"]):
                b = open_[e["tid"]].pop()
                out.append(Span(b["name"], at(b["ts"]), at(e["ts"]),
                                b.get("args", {})))
        return out

    def summary(self) -> str:
        parts = ", ".join(f"{n} {s:.3f}s" for n, s in self.phases)
        c = self.setup_compiles
        return (f"bench: setup {self.setup_s:.3f}s ({parts}); "
                f"{c['count']} compilations ({c['seconds']:.3f}s), "
                f"{c['cache_hits']} persistent-cache hits; window "
                f"{self.window[1] - self.window[0]:.3f}s with "
                f"{self.window_compiles} compilations inside it")


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)
