"""The device trace of a traced run, and its reduction to numbers.

``capture`` reads the JAX profiler's ``.xplane.pb`` into a plain record:
the device operations (one list per chip) and the benchmark's own host
annotations, all in seconds on the host's ``perf_counter`` clock. The
reduction functions below work on that record alone, so a small recorded
trace checks them without a chip.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: host annotations the benchmark writes into the profiler's trace
ANNOTATION_PREFIX = "bench:"
#: the annotation that spans the measured window; its start ties the
#: profiler's clock to the host's perf_counter
WINDOW_ANNOTATION = ANNOTATION_PREFIX + "window"
#: the line of a device plane that holds one event per executed operation
OPS_LINE = "XLA Ops"
#: the custom call a compiled Pallas kernel runs as
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
#: the replay kernels as ``op_name`` marks them: the schedule replay
#: returns cost, feasibility and summed completion time; the traffic
#: replay cost, misses, summed latency and per-request latency
SCHEDULE_KERNEL = "pallas[3 outputs]"
TRAFFIC_KERNEL = "pallas[4 outputs]"


def _outputs(text: str) -> int:
    """How many arrays an HLO instruction's text says it returns."""
    rhs = text.split(" = ", 1)[1] if " = " in text else text
    if not rhs.startswith("("):
        return 1
    depth, count = 0, 1
    for ch in rhs:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
            if depth == 0:
                return count
        elif ch == "," and depth == 1:
            count += 1
    return count


def op_name(text: str) -> str:
    """A device operation's short name. The profiler names an operation
    by its whole HLO instruction; keep the instruction's name, and mark a
    Pallas kernel with the number of arrays it returns, which tells the
    planner's two replay kernels apart (their HLO carries no kernel
    name)."""
    head = text.split(" = ", 1)[0]
    if PALLAS_TARGET in text:
        return f"{head} pallas[{_outputs(text)} outputs]"
    return head


def capture(logdir: str, window_t0: float) -> dict:
    """Read the newest trace under ``logdir``. ``window_t0`` is the
    perf_counter reading taken as the window annotation opened."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {logdir}")
    data = ProfileData.from_file(paths[-1])
    ops: Dict[int, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    anchor_ns: Optional[float] = None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            rows = ops.setdefault(dev, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    rows.append((op_name(ev.name), ev.start_ns,
                                 ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith(ANNOTATION_PREFIX):
                        continue
                    if ev.name == WINDOW_ANNOTATION:
                        anchor_ns = ev.start_ns
                    spans.append((ev.name[len(ANNOTATION_PREFIX):],
                                  ev.start_ns, ev.duration_ns))
    if anchor_ns is None:
        raise RuntimeError("the trace lacks the window annotation")
    to_s = lambda ns: window_t0 + (ns - anchor_ns) * 1e-9  # noqa: E731
    return {
        "devices": {str(d): [[n, to_s(s), dur * 1e-9] for n, s, dur in rows]
                    for d, rows in sorted(ops.items())},
        "spans": [[n, to_s(s), to_s(s) + dur * 1e-9]
                  for n, s, dur in spans],
    }


def _clip(intervals: Iterable[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    out = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_intervals(trace: dict, device: str, window: Sequence[float]
                   ) -> List[Tuple[float, float]]:
    """The union of the device's operation intervals inside the window."""
    return _clip(((s, s + d) for _, s, d in trace["devices"].get(device, [])),
                 window[0], window[1])


def busy_s(trace: dict, window: Sequence[float]) -> Optional[float]:
    """Seconds in which an operation ran, averaged over the chips."""
    devs = list(trace["devices"])
    if not devs:
        return None
    return sum(sum(b - a for a, b in busy_intervals(trace, d, window))
               for d in devs) / len(devs)


def op_seconds(trace: dict, window: Sequence[float],
               match=lambda name: True) -> Dict[str, float]:
    """Device seconds per operation name inside the window, summed over
    the chips, for the names ``match`` accepts."""
    out: Dict[str, float] = {}
    for rows in trace["devices"].values():
        for name, s, d in rows:
            if not match(name):
                continue
            a, b = max(s, window[0]), min(s + d, window[1])
            if b > a:
                out[name] = out.get(name, 0.0) + (b - a)
    return out


def idle_gaps(trace: dict, window: Sequence[float],
              spans: Sequence[Sequence]) -> List[Tuple[str, float]]:
    """Each stretch of the window in which the first chip ran nothing,
    named by the innermost host span open at its middle."""
    devs = sorted(trace["devices"], key=int)
    if not devs:
        return []
    busy = busy_intervals(trace, devs[0], window)
    gaps, t = [], window[0]
    for a, b in busy + [(window[1], window[1])]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    out = []
    for a, b in gaps:
        mid = 0.5 * (a + b)
        open_ = [s for s in spans if s[1] <= mid <= s[2]]
        name = max(open_, key=lambda s: s[1])[0] if open_ else "host"
        out.append((name, b - a))
    return out


def breakdown(trace: dict, window: Sequence[float],
              spans: Sequence[Sequence], top: int = 10) -> dict:
    ops = sorted(op_seconds(trace, window).items(), key=lambda kv: -kv[1])
    gaps = sorted(idle_gaps(trace, window, spans), key=lambda g: -g[1])
    return {"device_ops": [[n, s] for n, s in ops[:top]],
            "idle_gaps": [[n, s] for n, s in gaps[:top]]}



def kernel_seconds(run, kernel: str) -> float:
    """Device seconds the kernel ran inside the run's window, summed over
    the chips."""
    return sum(op_seconds(run.trace, run.window,
                          lambda name: name.endswith(kernel)).values())
