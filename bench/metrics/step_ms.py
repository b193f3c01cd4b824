"""Model step: device time of the jitted serve step, per step, averaged
over the steps in the traced slice."""
from bench.trace_reduce import module_seconds

PROGRAM = "_step"


def read(run):
    if run.trace is None:
        return None
    d = module_seconds(run.trace, PROGRAM)
    return 1e3 * sum(d) / len(d) if d else None
