"""Whole step: the model FLOPs the window's steps required (``bench.flops``)
over the window's length times the chip's peak."""
from bench.flops import step_flops


def read(run):
    model = run.config["model"]
    layers = model["num_hidden_layers"]
    flops = sum(step_flops(model, layers, s.fed) for s in run.window_steps)
    return 100.0 * flops / (run.seconds * run.peaks["bf16_flops"])
