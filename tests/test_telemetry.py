"""Unified telemetry layer (repro.core.telemetry, DESIGN.md §13): the
metric primitives, the registry's thread-safety and exporters, the span
tracer's Chrome trace-event schema, and the integration invariants the
layer rests on — telemetry OFF is bit-identical to the seed behavior,
telemetry ON agrees with every legacy counter surface
(``ServiceReport.counters`` / ``PlanCache.stats()`` /
``runner_cache_stats()``), and a chaos run exports a trace that the CI
validator (scripts/check_trace.py) accepts."""
import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

from repro.core import (ChaosConfig, PSOGAConfig, PlanCacheConfig,
                        ReplanConfig, ServiceConfig, Telemetry,
                        get_telemetry, maybe_span, run_service,
                        run_services, sample_environment, sample_trace,
                        set_telemetry, telemetry_scope,
                        zero_drift_trace)
from repro.core.dag import LayerDAG
from repro.core.telemetry import (Counter, Gauge, Histogram,
                                  MetricsRegistry, Series, SpanTracer)

#: distinct from every other test config so this file's first solve is a
#: fresh runner-cache entry
FAST = PSOGAConfig(pop_size=19, max_iters=40, stall_iters=15)
RCFG = ReplanConfig(pso=FAST)


def _tiny_dag(env, pin):
    return LayerDAG(
        compute=np.array([1.1, 1.92, 2.35, 2.12]) * env.power[0],
        edges=np.array([[0, 1], [0, 2], [1, 3], [2, 3]]),
        edge_mb=np.array([1.0, 1.0, 0.5, 0.5]),
        app_id=np.zeros(4, np.int32), deadline=np.array([3.7]),
        pinned=np.array([pin, -1, -1, -1], np.int32))


@pytest.fixture(scope="module")
def tiny_fleet():
    env = sample_environment()
    return env, [_tiny_dag(env, 0), _tiny_dag(env, 1)]


def _check_trace_module():
    """Import scripts/check_trace.py — the schema tests exercise the CI
    gate itself instead of a parallel reimplementation."""
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "scripts", "check_trace.py")
    spec = importlib.util.spec_from_file_location("check_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class FakeClock:
    """Deterministic clock: advances by ``step`` on every call."""

    def __init__(self, step: float = 0.001):
        self.t = 0.0
        self.step = step

    def __call__(self) -> float:
        self.t += self.step
        return self.t


# ---------------------------------------------------------------------------
# metric primitives
# ---------------------------------------------------------------------------

def test_counter_is_monotonic():
    c = Counter("x")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)
    assert c.value == 5


def test_gauge_last_value_wins():
    g = Gauge("x")
    g.set(3.5)
    g.set(-1.0)
    assert g.value == -1.0


def test_histogram_exact_moments_and_quantiles():
    h = Histogram("lat")
    for v in range(1, 101):
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 100 and s["sum"] == pytest.approx(5050.0)
    assert s["min"] == 1.0 and s["max"] == 100.0
    # reservoir holds everything below capacity: quantiles are exact
    assert s["p50"] == pytest.approx(50.5)
    assert s["p95"] == pytest.approx(np.percentile(np.arange(1, 101), 95))
    assert h.quantile(0) == 1.0 and h.quantile(100) == 100.0


def test_histogram_reservoir_is_bounded_and_deterministic():
    h1, h2 = Histogram("b", reservoir=64), Histogram("b", reservoir=64)
    for v in range(10_000):
        h1.observe(float(v))
        h2.observe(float(v))
    assert len(h1._res) == 64                 # bounded under pressure
    assert h1.count == 10_000                 # exact count survives
    assert h1.summary()["sum"] == pytest.approx(sum(range(10_000)))
    # per-name seeded sampling: identical runs sample identically
    assert h1.summary() == h2.summary()
    with pytest.raises(ValueError, match="reservoir"):
        Histogram("bad", reservoir=0)


def test_series_bounds_and_extend():
    s = Series("gbest", max_points=8)
    s.extend(100.0, np.arange(12.0))
    assert s.summary() == {"n": 8, "dropped": 4, "last": 11.0}
    ts = [t for t, _ in s.points()]
    assert ts == sorted(ts)                   # sub-ticks keep order


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_get_or_create_and_kind_conflict():
    r = MetricsRegistry()
    assert r.counter("a") is r.counter("a")
    with pytest.raises(TypeError, match="Counter"):
        r.gauge("a")


def test_registry_thread_safety():
    r = MetricsRegistry()
    n_threads, n_ops = 8, 1000

    def work():
        for i in range(n_ops):
            r.inc("c")
            r.observe("h", float(i))
            r.set_gauge("g", float(i))

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert r.counter("c").value == n_threads * n_ops
    assert r.histogram("h").count == n_threads * n_ops


def test_registry_exports_parse():
    r = MetricsRegistry()
    r.inc("svc.rounds", 3)
    r.set_gauge("svc.depth", 2.0)
    r.observe("svc.wall", 0.25)
    r.record_series("svc.gbest", [3.0, 2.0, 1.0])
    for line in r.to_jsonl().splitlines():
        rec = json.loads(line)
        assert {"type", "name"} <= set(rec)
    prom = r.to_prometheus()
    assert "svc_rounds_total 3" in prom
    assert 'svc_wall{quantile="0.5"}' in prom
    assert "svc_gbest_last 1.0" in prom
    snap = r.snapshot()
    assert snap["counters"]["svc.rounds"] == 3
    assert snap["series"]["svc.gbest"]["n"] == 3


def test_registry_write_files(tmp_path):
    r = MetricsRegistry()
    r.inc("a")
    paths = r.write(str(tmp_path / "m"))
    assert json.loads(open(paths["jsonl"]).read())["name"] == "a"
    assert "# TYPE" in open(paths["prom"]).read()


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_tracer_emits_paired_nested_spans():
    clk = FakeClock()
    tr = SpanTracer(clock=clk)
    with tr.span("outer", round=1):
        with tr.span("inner"):
            tr.instant("hit", key="k")
    evs = tr.events()
    assert [e["ph"] for e in evs] == ["B", "B", "i", "E", "E"]
    assert [e["name"] for e in evs] == ["outer", "inner", "hit",
                                       "inner", "outer"]
    for e in evs:
        assert {"ph", "ts", "pid", "tid", "name"} <= set(e)
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts) and ts[0] >= 0.0
    assert evs[0]["args"] == {"round": 1}
    assert evs[2]["s"] == "t"


def test_tracer_span_closes_on_exception():
    tr = SpanTracer(clock=FakeClock())
    with pytest.raises(RuntimeError):
        with tr.span("risky"):
            raise RuntimeError("boom")
    assert [e["ph"] for e in tr.events()] == ["B", "E"]


def test_tracer_tracks_are_thread_local():
    tr = SpanTracer(clock=time.perf_counter)
    tr.set_track(7, label="service-7")

    def other():
        tr.set_track(9)
        with tr.span("theirs"):
            pass

    t = threading.Thread(target=other)
    t.start()
    t.join()
    with tr.span("mine"):
        pass
    by_name = {e["name"]: e for e in tr.events() if e["ph"] == "B"}
    assert by_name["theirs"]["tid"] == 9
    assert by_name["mine"]["tid"] == 7
    meta = [e for e in tr.events() if e["ph"] == "M"]
    assert meta[0]["args"]["name"] == "service-7"
    assert meta[0]["tid"] == 7


def test_tracer_export_is_chrome_trace(tmp_path):
    tr = SpanTracer(clock=FakeClock())
    with tr.span("round"):
        pass
    path = str(tmp_path / "t.json")
    tr.export(path)
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    assert len(doc["traceEvents"]) == 2


def test_maybe_span_off_is_nullcontext():
    with maybe_span(None, "anything", round=3):
        pass  # no telemetry: must be free and silent
    tel = Telemetry(clock=FakeClock())
    with maybe_span(tel, "real"):
        pass
    assert len(tel.tracer.events()) == 2


def test_global_telemetry_scope():
    assert get_telemetry() is None
    tel = Telemetry(clock=FakeClock())
    with telemetry_scope(tel):
        assert get_telemetry() is tel
        with telemetry_scope(None):
            assert get_telemetry() is None
        assert get_telemetry() is tel
    assert get_telemetry() is None


# ---------------------------------------------------------------------------
# service integration: parity, agreement, determinism, schema
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chaos_pair(tiny_fleet):
    """One 10-round chaos service run with telemetry, one without —
    shared by the parity / agreement / schema tests."""
    env, dags = tiny_fleet
    trace = sample_trace("wifi-fade", env, rounds=10, seed=3)
    cfg = ServiceConfig(
        replan=RCFG, plan_cache=PlanCacheConfig(),
        # the straggler detector flags on MEASURED walls, which a loaded
        # host can skew differently across the two paired runs — keep it
        # in warmup so every counter compared below is deterministic
        straggler_warmup=100,
        chaos=ChaosConfig(crash_rounds=(2,), nan_env_rounds=(4,),
                          mid_round_down={6: 1}))
    tel = Telemetry()
    with_tel = run_service(dags, trace, cfg, seed=7, telemetry=tel)
    without = run_service(dags, trace, cfg, seed=7)
    return tel, with_tel, without


def test_service_telemetry_off_parity(chaos_pair):
    """The off-parity invariant: telemetry observes, never steers."""
    _, a, b = chaos_pair
    assert a.counters == b.counters
    assert a.fallback_counts == b.fallback_counts
    assert a.cache_stats == b.cache_stats
    for ra, rb in zip(a.rounds, b.rounds):
        assert ra.rung == rb.rung and ra.label == rb.label
        assert ra.breaker_state == rb.breaker_state
        assert ra.solver_failed == rb.solver_failed
        assert ra.stale_env == rb.stale_env
        assert ra.cache_hit == rb.cache_hit
    for pa, pb in zip(a.plans, b.plans):
        assert np.array_equal(pa, pb)


def test_service_counters_agree_with_registry(chaos_pair):
    """ONE pipeline: the registry snapshot and the legacy dict surfaces
    must tell the same story."""
    tel, rep, _ = chaos_pair
    snap = tel.registry.snapshot()
    for name, v in rep.counters.items():
        assert snap["counters"].get(f"service.{name}", 0) == v, name
    for rung, v in rep.fallback_counts.items():
        assert snap["counters"].get(f"service.rung.{rung}", 0) == v, rung
    for name, v in rep.cache_stats.items():
        assert snap["counters"].get(f"plancache.{name}", 0) == v, name


def test_service_trace_passes_ci_validator(chaos_pair, tmp_path):
    """Satellite: every span of a 10-round chaos run validates against
    the Chrome trace-event schema — via the actual CI gate."""
    tel, _, _ = chaos_pair
    path = str(tmp_path / "chaos_trace.json")
    tel.export_trace(path)
    tel.export_metrics(str(tmp_path / "m"))
    ct = _check_trace_module()
    n = ct.check_trace(path, require=["round", "solve", "cache_lookup",
                                      "ladder", "replan_round",
                                      "fleet_solve", "cold_solve"])
    assert n > 0
    ct.check_metrics(str(tmp_path / "m"))


def test_service_ingest_counters_always_present(chaos_pair):
    """Satellite regression: the ingest_* keys are part of the stable
    counter schema even with ingestion unconfigured."""
    _, rep, without = chaos_pair
    for r in (rep, without):
        for k in ("ingest_enqueued", "ingest_dropped",
                  "ingest_drained", "ingest_leftover"):
            assert k in r.counters and r.counters[k] == 0


def test_service_walls_use_injectable_clock(tiny_fleet):
    """Satellite: with a fake telemetry clock every wall measurement is
    a deterministic multiple of the tick — and replays identically."""
    env, dags = tiny_fleet
    trace = zero_drift_trace(env, rounds=3)
    cfg = ServiceConfig(replan=RCFG)

    def run():
        tel = Telemetry(clock=FakeClock(step=0.001))
        rep = run_service(dags, trace, cfg, seed=7, telemetry=tel)
        return [r.wall_s for r in rep.rounds]

    walls_a, walls_b = run(), run()
    assert walls_a == walls_b                     # replayable timings
    for w in walls_a:
        assert w > 0.0
        assert round(w / 0.001) == pytest.approx(w / 0.001)


def test_run_services_shared_telemetry_tracks(tiny_fleet):
    """Thread-safety under run_services: two concurrent services share
    one telemetry and land on their own Perfetto tracks."""
    env, dags = tiny_fleet
    trace = zero_drift_trace(env, rounds=2)
    cfg = ServiceConfig(replan=RCFG)
    tel = Telemetry()
    reports = run_services([dags, dags], trace, cfg, seeds=5,
                           telemetry=tel)
    solo = run_service(dags, trace, cfg, seed=5)
    for rep in reports:
        assert rep.counters == solo.counters
        for x, x_solo in zip(rep.plans, solo.plans):
            assert np.array_equal(x, x_solo)
    evs = tel.tracer.events()
    labels = {e["tid"]: e["args"]["name"] for e in evs
              if e["ph"] == "M"}
    assert labels == {0: "service-0", 1: "service-1"}
    span_tids = {e["tid"] for e in evs if e["ph"] == "B"}
    assert span_tids == {0, 1}
    # per-track B/E pairing survives the interleaving
    for tid in (0, 1):
        stack = []
        for e in evs:
            if e["tid"] != tid:
                continue
            if e["ph"] == "B":
                stack.append(e["name"])
            elif e["ph"] == "E":
                assert stack and stack.pop() == e["name"]
        assert stack == []
    # both services' rounds aggregate into one registry
    snap = tel.registry.snapshot()
    assert snap["histograms"]["service.round_wall_s"]["count"] == \
        2 * len(solo.rounds)


def test_telemetry_overhead_is_small(tiny_fleet):
    """Telemetry ON must not meaningfully slow the service. The bench
    (benchmarks/bench_service.py) stamps the precise number; here we
    only guard against a pathological regression."""
    env, dags = tiny_fleet
    trace = zero_drift_trace(env, rounds=3)
    cfg = ServiceConfig(replan=RCFG)
    run_service(dags, trace, cfg, seed=9)         # warm the jit caches
    t0 = time.perf_counter()
    run_service(dags, trace, cfg, seed=9)
    base = time.perf_counter() - t0
    t0 = time.perf_counter()
    run_service(dags, trace, cfg, seed=9, telemetry=Telemetry())
    instrumented = time.perf_counter() - t0
    assert instrumented < base * 1.25 + 0.05


def test_solver_history_becomes_series(tiny_fleet):
    """record_history publishes the gBest convergence curve as the
    ``solver.gbest`` metric series."""
    from repro.core import run_pso_ga
    env, dags = tiny_fleet
    cfg = PSOGAConfig(pop_size=8, max_iters=12, stall_iters=12)
    tel = Telemetry()
    res = run_pso_ga(dags[0], env, cfg, seed=1, record_history=True,
                     telemetry=tel)
    pts = tel.registry.series("solver.gbest").points()
    assert [v for _, v in pts] == [float(v) for v in res.history]
    assert tel.registry.counter("solver.history_runs").value == 1


def test_global_channel_reaches_deep_layers(tiny_fleet):
    """The runner cache and solver history have no config path: the
    process-global channel is how they join the session's telemetry."""
    env, dags = tiny_fleet
    trace = zero_drift_trace(env, rounds=2)
    tel = Telemetry()
    with telemetry_scope(tel):
        run_service(dags, trace, ServiceConfig(replan=RCFG), seed=3)
    snap = tel.registry.snapshot()
    lookups = (snap["counters"].get("runner_cache.lookup_hits", 0)
               + snap["counters"].get("runner_cache.lookup_misses", 0))
    assert lookups > 0
    assert "service.round_wall_s" in snap["histograms"]
    assert set_telemetry(None) is None            # scope restored


# ---------------------------------------------------------------------------
# stage spans inside the fleet solver, compile spans, the profiler's clock
# ---------------------------------------------------------------------------

def _span_tree(events):
    """(name, args, children) roots of each track's B/E stream, with
    JAX's compile spans left out."""
    roots, stack = [], []
    for e in events:
        if e["ph"] == "B":
            node = (e["name"], e.get("args", {}), [])
            (stack[-1][2] if stack else roots).append(node)
            stack.append(node)
        elif e["ph"] == "E":
            assert stack and stack.pop()[0] == e["name"]
    assert not stack

    def strip(nodes):
        return [(n, a, strip(c)) for n, a, c in nodes
                if not n.startswith("jit_")]
    return strip(roots)


@pytest.fixture(scope="module")
def two_bucket_fleet(tiny_fleet):
    """Two 4-layer DAGs (bucket 8) and one 12-layer chain (bucket 16)."""
    env, dags = tiny_fleet
    p = 12
    chain = LayerDAG(
        compute=np.linspace(1.0, 2.0, p) * env.power[0],
        edges=np.array([[i, i + 1] for i in range(p - 1)]),
        edge_mb=np.ones(p - 1), app_id=np.zeros(p, np.int32),
        deadline=np.array([9.0]),
        pinned=np.array([2] + [-1] * (p - 1), np.int32))
    return [(dags[0], env), (chain, env), (dags[1], env)]


def test_fleet_batch_stage_spans_nest(two_bucket_fleet):
    """One ``fleet_batch`` holds ``fleet_pack``, then per bucket the
    five stages in order, then ``gather_state``: a span per stage and
    bucket, never per problem."""
    from repro.core import run_pso_ga_batch
    tel = Telemetry()
    run_pso_ga_batch(two_bucket_fleet, FAST, seed=[1, 2, 3],
                     return_state=True, telemetry=tel)
    (name, args, children), = _span_tree(tel.tracer.events())
    assert name == "fleet_batch"
    assert args == {"n": 3, "buckets": 2}
    stages = ["swarm_init", "fleet_inputs", "fleet_solve", "resimulate",
              "unpack"]
    assert [c[0] for c in children] == \
        ["fleet_pack"] + stages * 2 + ["gather_state"]
    per_bucket = [(c[1]["bucket"], c[1]["n"]) for c in children[1:-1]]
    assert per_bucket == [("8x8", 2)] * 5 + [("16x8", 1)] * 5
    assert all(not c[2] for c in children)


def test_fleet_batch_telemetry_off_parity(two_bucket_fleet):
    """Stage spans observe: results and the returned swarm state are
    bit-identical with telemetry on and off."""
    from repro.core import run_pso_ga_batch
    off, st_off = run_pso_ga_batch(two_bucket_fleet, FAST, seed=[4, 5, 6],
                                   return_state=True)
    on, st_on = run_pso_ga_batch(two_bucket_fleet, FAST, seed=[4, 5, 6],
                                 return_state=True, telemetry=Telemetry())
    for a, b in zip(off, on):
        assert np.array_equal(a.best_x, b.best_x)
        assert (a.best_fitness, a.best_cost, a.feasible, a.iterations) \
            == (b.best_fitness, b.best_cost, b.feasible, b.iterations)
    for a, b in zip(st_off, st_on):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_fresh_jit_becomes_compile_span():
    """JAX's own compile events land as ``jit_*`` spans nested in the
    span open on the compiling thread, and count ``jit.compiles``."""
    import jax
    import jax.numpy as jnp
    tel = Telemetry()
    fresh = jax.jit(lambda x: jnp.sin(x) * 3.25 + 0.125)
    x = jnp.arange(7.0).block_until_ready()
    with tel.span("stage"):
        fresh(x).block_until_ready()
    evs = tel.tracer.events()
    assert [evs[0]["ph"], evs[0]["name"]] == ["B", "stage"]
    assert [evs[-1]["ph"], evs[-1]["name"]] == ["E", "stage"]
    compiles = [(b, e) for b, e in zip(evs, evs[1:])
                if b["ph"] == "B" and b["name"] == "jit_compile"]
    assert compiles, [e["name"] for e in evs]
    b, e = next((b, e) for b, e in compiles
                if "<lambda>" in b["args"]["fun"])
    assert e["ph"] == "E" and e["name"] == "jit_compile"
    assert 0.0 <= b["ts"] <= e["ts"] <= evs[-1]["ts"]
    assert tel.registry.counter("jit.compiles").value == len(compiles)
    # nothing open on this thread and no global channel: dropped
    jax.jit(lambda x: x - 0.375)(jnp.ones(3)).block_until_ready()
    assert len(tel.tracer.events()) == len(evs)


def test_compile_spans_use_the_global_channel_outside_spans():
    import jax
    import jax.numpy as jnp
    tel = Telemetry()
    with telemetry_scope(tel):
        jax.jit(lambda x: x * 0.625)(jnp.ones(5)).block_until_ready()
    names = [e["name"] for e in tel.tracer.events() if e["ph"] == "B"]
    assert "jit_compile" in names


def test_compile_spans_clamp_to_the_tracer_start():
    tr = SpanTracer(clock=FakeClock())
    tr.span_at("jit_compile", time.time() - 100.0, time.time() - 99.0,
               fun="old")
    b, e = tr.events()
    assert b["ts"] == e["ts"] == 0.0


def test_short_traces_are_no_spans_short_lowerings_are():
    """A jaxpr trace under ``MIN_TRACE_SPAN_S`` is dropped; a lowering or
    a backend compile of any length becomes a span."""
    from repro.core import telemetry as tm
    tel = Telemetry()
    now = time.time()
    short = 0.5 * tm.MIN_TRACE_SPAN_S
    with tel.span("stage"):
        for event, dur in (("jaxpr_trace_duration", short),
                           ("jaxpr_trace_duration", 2 * tm.MIN_TRACE_SPAN_S),
                           ("jaxpr_to_mlir_module_duration", short),
                           ("backend_compile_duration", short)):
            tm._on_time_span(f"/jax/core/compile/{event}", now - dur, now,
                             fun_name=event)
    kept = [(e["name"], e["args"]["fun"]) for e in tel.tracer.events()
            if e["ph"] == "B" and e["name"].startswith("jit_")
            and e["args"]["fun"].endswith("_duration")]
    assert kept == [("jit_trace", "jaxpr_trace_duration"),
                    ("jit_lower", "jaxpr_to_mlir_module_duration"),
                    ("jit_compile", "backend_compile_duration")]


def test_no_listener_before_the_first_telemetry():
    """Importing the planner and solving with telemetry off registers
    no JAX listener; the first ``Telemetry`` registers exactly one."""
    import subprocess
    import sys
    code = """
import numpy as np
from jax._src import monitoring
from repro.core import (PSOGAConfig, Telemetry, run_pso_ga_batch,
                        sample_environment, telemetry)
from repro.core.dag import LayerDAG
env = sample_environment()
dag = LayerDAG(compute=np.ones(3) * env.power[0],
               edges=np.array([[0, 1], [1, 2]]), edge_mb=np.ones(2),
               app_id=np.zeros(3, np.int32), deadline=np.array([9.0]),
               pinned=np.array([0, -1, -1], np.int32))
run_pso_ga_batch([(dag, env)], PSOGAConfig(pop_size=4, max_iters=2))
spans = monitoring.get_event_time_span_listeners
assert telemetry._on_time_span not in spans(), spans()
Telemetry()
Telemetry()
assert spans().count(telemetry._on_time_span) == 1, spans()
assert monitoring.get_event_listeners().count(telemetry._on_event) == 1
"""
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]


def test_spans_reach_the_profiler_trace(tmp_path):
    """Under ``jax.profiler.trace`` each span is also a host annotation,
    on the profiler's clock beside the device operations."""
    import glob
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    tel = Telemetry()
    with jax.profiler.trace(str(tmp_path)):
        with tel.span("outer_stage"):
            with tel.span("inner_stage"):
                jnp.ones(4).sum().block_until_ready()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"outer_stage", "inner_stage"} <= names


def test_check_trace_accepts_compile_spans(two_bucket_fleet, tmp_path):
    """An export holding compile spans (B/E pairs appended after the
    fact) still validates against the CI gate."""
    import jax
    import jax.numpy as jnp
    from repro.core import run_pso_ga_batch
    tel = Telemetry()
    with tel.span("stage"):
        jax.jit(lambda x: x * 1.875)(jnp.ones(2)).block_until_ready()
    run_pso_ga_batch(two_bucket_fleet, FAST, seed=7, telemetry=tel)
    path = str(tmp_path / "trace.json")
    tel.export_trace(path)
    n = _check_trace_module().check_trace(
        path, require=["jit_compile", "fleet_batch", "swarm_init",
                       "resimulate"])
    assert n > 0
