"""Host milliseconds per problem that a service round's fleet solve
spends outside ``fleet_solve``: the program's ``warm_solve`` span around
``run_pso_ga_batch`` minus the device solves inside it."""


def read(run):
    warm = run.spans_named("warm_solve")
    solves = run.spans_named("fleet_solve")
    if run.kind != "service" or not warm or not solves:
        return None
    inside = sum(s.t1 - s.t0 for s in solves
                 if any(w.t0 <= s.t0 and s.t1 <= w.t1 for w in warm))
    host = sum(w.t1 - w.t0 for w in warm) - inside
    return 1e3 * host / (len(warm) * len(run.layers))
