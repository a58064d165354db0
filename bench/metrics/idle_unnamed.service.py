"""Percent of the first chip's idle time in the window that no stage
span explains: no span is open, or the innermost one is a container
(``service_round``, ``round``, ``solve``, ``replan_round``,
``warm_solve``, ``fleet_batch``)."""
from harness import stages


def read(run):
    if run.kind != "service":
        return None
    return stages.idle_unnamed(run)
