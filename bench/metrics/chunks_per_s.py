"""Chunks whose summaries reached the host inside the window, per second."""


def read(run):
    w0, w1 = run.window
    return float(((run.done >= w0) & (run.done <= w1)).sum()) / run.seconds
