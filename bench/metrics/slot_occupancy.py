"""Scheduler: slots held by a request, as a share of all slots, averaged
over the window's steps."""


def read(run):
    steps = run.window_steps
    if not steps:
        return None
    return 100.0 * sum(s.busy for s in steps) / (len(steps) * run.slots)
