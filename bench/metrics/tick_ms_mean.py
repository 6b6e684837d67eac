"""Mean time of one engine tick: ``step`` and the fetch of its summaries."""


def read(run):
    if not run.ticks:
        return None
    return sum(e - s for s, e, _ in run.ticks) / len(run.ticks) * 1e3
