"""Milliseconds per fleet call inside the program's ``fleet_solve``
spans (one per shape bucket, each ending in ``block_until_ready``)."""


def read(run):
    calls = run.spans_named("fleet_call")
    solves = run.spans_named("fleet_solve")
    if run.kind != "fleet" or not calls or not solves:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in solves) / len(calls)
