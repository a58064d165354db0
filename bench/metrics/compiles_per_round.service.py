"""JAX compilations inside the window per service round, counted by
JAX's own compile events (persistent-cache hits included)."""


def read(run):
    if run.kind != "service" or not run.steps:
        return None
    return run.compiles_in_window / len(run.steps)
