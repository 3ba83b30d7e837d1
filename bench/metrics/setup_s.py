"""Seconds from process start to the start of the measured window:
loading, building inputs and weights, compiling or loading from the cache,
and warming up every shape of the window."""


def read(run):
    return run.setup_s
