"""Host milliseconds per problem that a fleet call spends outside the
program's ``fleet_solve`` span: the benchmark's span around each
``run_pso_ga_batch`` call minus the device solves inside it."""


def read(run):
    calls = run.spans_named("fleet_call")
    solves = run.spans_named("fleet_solve")
    if run.kind != "fleet" or not calls or not solves:
        return None
    host = (sum(c.t1 - c.t0 for c in calls)
            - sum(s.t1 - s.t0 for s in solves))
    return 1e3 * host / (len(calls) * len(run.layers))
