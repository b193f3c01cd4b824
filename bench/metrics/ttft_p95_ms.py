"""95th percentile of time to first token over the window's requests."""
from bench.stats import percentile, ttfts


def read(run):
    v = percentile(ttfts(run), 95)
    return None if v is None else v * 1e3
