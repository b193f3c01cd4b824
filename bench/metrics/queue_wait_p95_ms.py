"""Scheduler: 95th percentile of the wait from a window request's due
time to the start of the step in which it first held a slot."""
from bench.stats import percentile, queue_waits


def read(run):
    v = percentile(queue_waits(run), 95)
    return None if v is None else v * 1e3
