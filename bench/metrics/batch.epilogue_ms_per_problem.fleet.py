"""Host milliseconds per problem that a fleet call spends after the
device solve: the ``resimulate`` spans (each bucket's best plans
replayed again) and the ``unpack`` spans (each problem's result read
back from the device)."""
from harness import stages


def read(run):
    if run.kind != "fleet":
        return None
    return stages.ms_per_problem(run, "fleet_call", "resimulate", "unpack")
