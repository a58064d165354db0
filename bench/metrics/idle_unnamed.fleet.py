"""Percent of the first chip's idle time in the window that no stage
span explains: no span is open, or the innermost one is a container
(the benchmark's ``fleet_call``, the program's ``fleet_batch``)."""
from harness import stages


def read(run):
    if run.kind != "fleet":
        return None
    return stages.idle_unnamed(run)
