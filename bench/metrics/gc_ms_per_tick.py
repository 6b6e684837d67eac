"""Python GC pauses inside a tick, per tick.

The mean over the window's ticks of ``TickMetrics.gc_s``.
"""


def read(run):
    gc_s = [getattr(m, "gc_s", None) for m in run.tick_metrics]
    if not gc_s or None in gc_s:
        return None
    return sum(gc_s) / len(gc_s) * 1e3
