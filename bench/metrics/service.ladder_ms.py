"""Milliseconds per round in the service's ``ladder`` span: every
problem's plan is validated and replayed before it is served."""


def read(run):
    ladders = run.spans_named("ladder")
    if run.kind != "service" or not ladders:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in ladders) / len(ladders)
