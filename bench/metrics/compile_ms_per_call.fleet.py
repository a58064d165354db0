"""Milliseconds per fleet call in which JAX traced, lowered or compiled
a program: the union of the program's ``jit_trace``, ``jit_lower`` and
``jit_compile`` spans, made from JAX's own compile events."""
from harness import stages


def read(run):
    if run.kind != "fleet":
        return None
    return stages.compile_ms_per(run, "fleet_call")
