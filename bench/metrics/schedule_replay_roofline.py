"""The schedule-replay kernel's share of its roofline over the window:
the least time the chip needs for the replays the solver asked for (one
per particle per live iteration, plus the initial swarm), counted from
shapes, over the kernel's device time in the trace."""
from harness import peaks
from harness.tracing import SCHEDULE_KERNEL, kernel_seconds


def read(run):
    if run.kind != "fleet" or run.trace is None:
        return None
    kernel_s = kernel_seconds(run, SCHEDULE_KERNEL)
    if not kernel_s:
        return None
    need = 0.0
    for step in run.steps:
        for layers, edges, it in zip(run.layers, run.edges, step.iterations):
            w = peaks.schedule_replay(layers, edges, run.servers, run.apps,
                                      run.pop_size)
            need += (it + 1) * w.seconds(run.device_kind)
    return 100.0 * need / kernel_s
