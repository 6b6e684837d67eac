"""Host time of a tick's uncertainty summaries, per tick.

The MC groups' summaries and the student heads (``engine.summarize``): the
mean over the window's ticks of ``TickMetrics.phase_s["engine.summarize"]``.
"""

from bench.engine_trace import phase_ms


def read(run):
    return phase_ms(run, "engine.summarize")
