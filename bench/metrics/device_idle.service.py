"""Share of the window in which no operation ran on the chips
(averaged over them), from the profiler trace."""
from harness import tracing


def read(run):
    if run.kind != "service" or run.trace is None:
        return None
    busy = tracing.busy_s(run.trace, run.window)
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / (run.window[1] - run.window[0]))
