"""Output tokens delivered to the host inside the window, over the
window's length."""
from bench.stats import window_tokens


def read(run):
    return window_tokens(run) / run.seconds
