"""Host milliseconds per problem that a service round spends in the
program's ``swarm_init`` spans: each problem's key split, warm-started
swarm and its embedding into the bucket's padded genes."""
from harness import stages


def read(run):
    if run.kind != "service":
        return None
    return stages.ms_per_problem(run, "service_round", "swarm_init")
