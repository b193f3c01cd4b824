"""Kernels: the paged attention kernel's share of its roofline. The work
is what the traced steps' requests needed (``bench.flops``), the time is
the kernel's device time in the trace."""
from bench.flops import paged_attention_work, roofline_seconds
from bench.trace_reduce import op_seconds

# the kernel's custom call is named after its entry point
KERNEL = "paged_decode_attention"


def read(run):
    if run.trace is None:
        return None
    t = op_seconds(run.trace, KERNEL)
    if t <= 0:
        return None
    layers = run.config["model"]["num_hidden_layers"]
    flops = nbytes = 0.0
    for s in run.steps:
        if s.traced:
            f, b = paged_attention_work(run.config["model"], layers, s.fed)
            flops += f
            nbytes += b
    need, _ = roofline_seconds(flops, nbytes, run.peaks)
    return 100.0 * need / t
