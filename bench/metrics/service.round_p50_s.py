"""Median seconds of the service's ``round`` span over the window."""
import statistics


def read(run):
    rounds = run.spans_named("round")
    if run.kind != "service" or not rounds:
        return None
    return statistics.median(s.t1 - s.t0 for s in rounds)
