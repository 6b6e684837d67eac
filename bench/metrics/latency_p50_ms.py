"""Median chunk latency: due time to summary on the host."""

from bench.stats import percentile


def read(run):
    w = run.in_window
    p = percentile(run.done[w] - run.due[w], 50)
    return None if p is None else p * 1e3
