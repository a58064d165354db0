"""Milliseconds per service round spent making the solver's inputs: the
``build_problems`` (each app's problem under the round's environment),
``draw_arrivals`` (the solver's request draws) and ``triage`` spans."""
from harness import stages


def read(run):
    rounds = run.spans_named("service_round")
    if run.kind != "service" or not rounds or not stages.instrumented(run):
        return None
    return 1e3 * stages.seconds(run, "build_problems", "draw_arrivals",
                                "triage") / len(rounds)
