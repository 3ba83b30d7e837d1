"""Global tokens of every train step in the window, over the time from the
window's start to the last step's end."""


def read(run):
    return sum(r["tokens"] for r in run.records) / run.window_s
