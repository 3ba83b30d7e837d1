"""Simulated node-iterations per second: samples x nodes x coupled
iterations of every sweep completed in the window, over the time from the
window's start to the last completion.  The healthy reference row and the
warm-up iterations are not counted."""


def read(run):
    return sum(r["work"] for r in run.records) / run.window_s
