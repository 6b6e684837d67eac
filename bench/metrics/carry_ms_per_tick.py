"""Host time of a tick's carry path, per tick.

Gathering the sessions' carries into the launch (``engine.carry_gather``)
and slicing the new carries and results back out per session
(``engine.writeback``): the mean over the window's ticks of the two
``TickMetrics.phase_s`` entries, summed.
"""

from bench.engine_trace import phase_ms


def read(run):
    return phase_ms(run, "engine.carry_gather", "engine.writeback")
