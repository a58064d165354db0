"""Milliseconds per service round in which JAX traced, lowered or
compiled a program: the union of the program's ``jit_trace``,
``jit_lower`` and ``jit_compile`` spans, made from JAX's own compile
events."""
from harness import stages


def read(run):
    if run.kind != "service":
        return None
    return stages.compile_ms_per(run, "service_round")
