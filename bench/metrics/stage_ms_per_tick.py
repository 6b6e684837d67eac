"""Host time of a tick's staging, per tick (``engine.stage``).

Chunk checks, the host batch arrays and their transfers to the device: the
mean over the window's ticks of ``TickMetrics.phase_s["engine.stage"]``.
"""

from bench.engine_trace import phase_ms


def read(run):
    return phase_ms(run, "engine.stage")
