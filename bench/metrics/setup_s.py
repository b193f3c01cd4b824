"""Set-up: process start to the window's start. Loading, building the
engine, making the weights, compiling or loading every step shape, and
the warm-up that fills the store."""


def read(run):
    return run.setup_s
