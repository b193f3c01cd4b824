"""Store: prompt tokens restored from the store over all prompt tokens
the engine took in the window (computed plus restored), from the
engine's own counters."""


def read(run):
    c = run.counters
    total = c.get("prefill_tokens", 0) + c.get("prefill_tokens_skipped", 0)
    if not total:
        return None
    return 100.0 * c["prefill_tokens_skipped"] / total
