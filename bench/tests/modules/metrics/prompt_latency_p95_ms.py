"""95th percentile of a turn's prompt latency, due time to summary on the
host: a chat cell's time to first token, read from the run alone.

A turn's prompt is the schedule entry with a fixed due time; the decode
steps chained to it have none.
"""

import numpy as np

from bench.stats import percentile


def read(run):
    due = run.schedule.due
    prompt = np.array([np.isfinite(due[s][j])
                       for s, j in zip(run.session, run.index)], bool)
    w = run.in_window & prompt
    p = percentile(run.done[w] - run.due[w], 95)
    return None if p is None else p * 1e3
