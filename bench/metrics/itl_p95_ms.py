"""95th percentile of the gaps between successive host-visible tokens of
a request, over all gaps (``bench.stats.token_gaps``)."""
from bench.stats import percentile, token_gaps


def read(run):
    v = percentile(token_gaps(run), 95)
    return None if v is None else v * 1e3
