"""Host milliseconds per problem that a service round spends after the
device solve: ``resimulate``, ``unpack`` and ``gather_state`` (the
swarm state put back in problem order for the re-planner)."""
from harness import stages


def read(run):
    if run.kind != "service":
        return None
    return stages.ms_per_problem(run, "service_round", "resimulate",
                                 "unpack", "gather_state")
