"""Store: effective hits over block accesses in the window (the paper's
metric), from the prefix store's own counters."""


def read(run):
    c = run.counters
    if not c.get("accesses"):
        return None
    return 100.0 * c["effective_hits"] / c["accesses"]
