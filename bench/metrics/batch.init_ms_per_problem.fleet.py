"""Host milliseconds per problem that a fleet call spends in the
program's ``swarm_init`` spans: each problem's key split, initial swarm
and its embedding into the bucket's padded genes."""
from harness import stages


def read(run):
    if run.kind != "fleet":
        return None
    return stages.ms_per_problem(run, "fleet_call", "swarm_init")
