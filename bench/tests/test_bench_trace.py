"""The reduction from a device trace to busy time, kernel time, idle gaps
and the breakdown, checked on a small trace recorded on a TPU v5e: the
first operations of a traced ``service.bursty10`` window."""
import json
from pathlib import Path

import pytest

from harness import tracing
from harness.tracing import (SCHEDULE_KERNEL, TRAFFIC_KERNEL,
                             kernel_seconds)

DATA = Path(__file__).resolve().parent / "data" / "service_trace_small.json"


@pytest.fixture(scope="module")
def recorded():
    d = json.loads(DATA.read_text())
    return d["trace"], tuple(d["window"]), d["spans"]


def _union_by_sweep(rows, lo, hi):
    """Busy seconds by a sweep over start and end events."""
    events = []
    for _, s, d in rows:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            events += [(a, 1), (b, -1)]
    events.sort()
    depth, busy, last = 0, 0.0, None
    for t, step in events:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_busy_time_is_the_union_of_operations(recorded):
    trace, window, _ = recorded
    assert trace["devices"], "the recorded trace has device operations"
    want = sum(_union_by_sweep(rows, *window)
               for rows in trace["devices"].values()) / len(trace["devices"])
    got = tracing.busy_s(trace, window)
    assert got == pytest.approx(want, rel=1e-9)
    assert 0.0 < got < window[1] - window[0]


def test_idle_gaps_and_busy_fill_the_window(recorded):
    trace, window, spans = recorded
    gaps = tracing.idle_gaps(trace, window, spans)
    first = sorted(trace["devices"], key=int)[0]
    busy = sum(b - a for a, b in tracing.busy_intervals(trace, first,
                                                        window))
    assert sum(g for _, g in gaps) + busy == pytest.approx(
        window[1] - window[0], rel=1e-9)
    named = {n for n, _ in gaps}
    assert named <= {s[0] for s in spans} | {"host"}
    assert named - {"host"}, "gaps are named by the host spans open"


def test_breakdown_lists_the_longest_first(recorded):
    trace, window, spans = recorded
    bd = tracing.breakdown(trace, window, spans)
    ops = [s for _, s in bd["device_ops"]]
    gaps = [s for _, s in bd["idle_gaps"]]
    assert ops == sorted(ops, reverse=True) and len(ops) <= 10
    assert gaps == sorted(gaps, reverse=True) and len(gaps) <= 10
    total = sum(tracing.op_seconds(trace, window).values())
    assert sum(ops) <= total + 1e-12


def test_kernel_time_is_found_by_name(recorded):
    trace, window, _ = recorded
    run = type("R", (), {"trace": trace, "window": window})()
    k = kernel_seconds(run, TRAFFIC_KERNEL)
    assert k > 0.0
    assert k <= sum(tracing.op_seconds(trace, window).values())
    assert kernel_seconds(run, SCHEDULE_KERNEL) == 0.0


def test_operations_outside_the_window_do_not_count(recorded):
    trace, window, _ = recorded
    mid = 0.5 * (window[0] + window[1])
    assert tracing.busy_s(trace, (window[0], mid)) <= \
        tracing.busy_s(trace, window)
    last = max(s + d for rows in trace["devices"].values()
               for _, s, d in rows)
    assert tracing.busy_s(trace, (last, last + 1.0)) == 0.0
    assert tracing.busy_s(trace, (window[0] - 1.0, window[0])) == 0.0


def test_op_names_are_short_and_mark_pallas_kernels():
    text = ('%vmap__.94 = (f32[4,1,100]{2,1,0:T(1,128)S(1)}, '
            'f32[4,1,100]{2,1,0}, f32[4,1,100]{2,1,0}) '
            'custom-call(s32[4,1,16]{2,1,0} %bitcast.265), '
            'custom_call_target="tpu_custom_call", frontend_attributes={}')
    assert tracing.op_name(text) == "%vmap__.94 " + SCHEDULE_KERNEL
    assert tracing.op_name("%while.3 = (u32[94,2]{0,1}, s32[3]) while(x)") \
        == "%while.3"
