"""The traffic-replay kernel's share of its roofline over the window:
the least time the chip needs for the replays a round asks for (each
particle over each of its solver draws, per live iteration plus the
initial swarm, and the incumbent's key over the same draws), counted
from shapes, over the kernel's device time in the trace."""
from harness import peaks
from harness.tracing import TRAFFIC_KERNEL, kernel_seconds


def read(run):
    if run.kind != "service" or run.trace is None:
        return None
    kernel_s = kernel_seconds(run, TRAFFIC_KERNEL)
    if not kernel_s:
        return None
    need = 0.0
    for step in run.steps:
        for i, (layers, edges) in enumerate(zip(run.layers, run.edges)):
            it = step.iterations[i] if step.iterations else 0
            for requests in step.draws[i]:
                def w(particles):
                    return peaks.traffic_replay(
                        layers, edges, run.servers, run.apps, requests,
                        run.max_requests, particles).seconds(run.device_kind)
                need += (it + 1) * w(run.pop_size) + w(1)
    return 100.0 * need / kernel_s
