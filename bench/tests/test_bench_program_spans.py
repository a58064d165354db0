"""The readers of the program's stage spans, each checked on a
hand-built run whose spans and device intervals give a known value."""
import pytest

from harness.session import Run, Span
from harness.spec import load_reader

#: (name, start, end) of one fleet call starting at t, seconds
FLEET_CALL = [("fleet_call", 0.0, 4.0), ("fleet_batch", 0.1, 3.9),
              ("fleet_pack", 0.1, 0.2), ("swarm_init", 0.2, 0.6),
              ("fleet_inputs", 0.6, 1.0), ("fleet_solve", 1.0, 2.0),
              ("resimulate", 2.0, 2.5), ("jit_trace", 2.1, 2.3),
              ("jit_compile", 2.2, 2.4), ("unpack", 2.5, 3.0)]
#: one service round starting at t
SERVICE_ROUND = [("service_round", 0.0, 5.0), ("round", 0.05, 4.95),
                 ("build_problems", 0.1, 0.3), ("draw_arrivals", 0.3, 0.4),
                 ("triage", 0.4, 0.5), ("solve", 0.5, 4.5),
                 ("replan_round", 0.5, 4.5), ("plan_check", 0.5, 0.6),
                 ("incumbent_keys", 0.6, 0.9), ("warm_solve", 0.9, 4.0),
                 ("fleet_batch", 0.9, 4.0), ("fleet_pack", 0.9, 1.0),
                 ("swarm_init", 1.0, 1.5), ("fleet_inputs", 1.5, 2.0),
                 ("fleet_solve", 2.0, 3.0), ("resimulate", 3.0, 3.2),
                 ("jit_compile", 3.05, 3.15), ("unpack", 3.2, 3.4),
                 ("gather_state", 3.4, 3.6), ("ladder", 4.6, 4.8)]


def _run(kind, steps, step_s, busy, n_problems, drop=()):
    spans = [Span(n, t + a, t + b, {}) for t in steps
             for n, a, b in (FLEET_CALL if kind == "fleet" else SERVICE_ROUND)
             if n not in drop]
    trace = {"devices": {"0": [["%while.1", t + a, b - a] for t in steps
                               for a, b in busy]},
             "spans": []}
    return Run(kind=kind, device_kind="TPU v5 lite",
               layers=[5] * n_problems, edges=[4] * n_problems, servers=20,
               apps=1, pop_size=100, max_requests=8, steps=[],
               window=(0.0, step_s * len(steps)), spans=spans,
               compiles_in_window=0, trace=trace)


@pytest.fixture
def fleet_run():
    # two calls of two problems, the chip busy only in fleet_solve
    return _run("fleet", [0.0, 4.0], 4.0, [(1.0, 2.0)], 2)


@pytest.fixture
def service_run():
    # two rounds of three problems
    return _run("service", [0.0, 5.0], 5.0, [(2.0, 3.0)], 3)


FLEET = {
    # 2 x 0.4 s over 2 calls x 2 problems
    "batch.init_ms_per_problem.fleet": 200.0,
    # 2 x (0.5 + 0.5) s over 4
    "batch.epilogue_ms_per_problem.fleet": 500.0,
    # the union of 2.1-2.3 and 2.2-2.4, twice, over 2 calls
    "compile_ms_per_call.fleet": 300.0,
    # idle 0-1 and 2-4 a call: 0.1 + 0.9 + 0.1 s of 3 s in fleet_call
    # or fleet_batch alone (fleet_pack opens with fleet_batch)
    "idle_unnamed.fleet": 100.0 * 2.2 / 6.0,
}
SERVICE = {
    # 2 x 0.5 s over 2 rounds x 3 problems
    "batch.init_ms_per_problem.service": 1000.0 / 6,
    # 2 x (0.2 + 0.2 + 0.2) s over 6
    "batch.epilogue_ms_per_problem.service": 200.0,
    "compile_ms_per_round.service": 100.0,
    # build_problems + draw_arrivals + triage, 0.4 s a round
    "service.inputs_ms": 400.0,
    # idle 0-2 and 3-5 a round: 0.1 + 1.2 s of 4 s in a container
    "idle_unnamed.service": 100.0 * 2.6 / 8.0,
}


@pytest.mark.parametrize("metric", sorted(FLEET))
def test_fleet_reader_reads_known_spans(metric, fleet_run, service_run):
    read = load_reader(metric)
    assert read(fleet_run) == pytest.approx(FLEET[metric], rel=1e-9)
    assert read(service_run) is None


@pytest.mark.parametrize("metric", sorted(SERVICE))
def test_service_reader_reads_known_spans(metric, fleet_run, service_run):
    read = load_reader(metric)
    assert read(service_run) == pytest.approx(SERVICE[metric], rel=1e-9)
    assert read(fleet_run) is None


@pytest.mark.parametrize("metric", sorted(FLEET) + sorted(SERVICE))
def test_reader_without_stage_spans_reads_nothing(metric):
    """A program that writes no stage spans (the parent of this change)
    gives no value, and no error, from every reader."""
    stages = {"fleet_batch", "fleet_pack", "swarm_init", "fleet_inputs",
              "resimulate", "unpack", "gather_state", "jit_trace",
              "jit_compile", "build_problems", "draw_arrivals", "triage",
              "plan_check"}
    runs = [_run("fleet", [0.0, 4.0], 4.0, [(1.0, 2.0)], 2, drop=stages),
            _run("service", [0.0, 5.0], 5.0, [(2.0, 3.0)], 3, drop=stages)]
    for run in runs:
        assert load_reader(metric)(run) is None


def test_idle_counts_only_the_first_chip(fleet_run):
    fleet_run.trace["devices"]["1"] = [["%while.1", 0.0, 8.0]]
    assert load_reader("idle_unnamed.fleet")(fleet_run) == \
        pytest.approx(FLEET["idle_unnamed.fleet"], rel=1e-9)
