"""Host time to dispatch the model, per tick (``engine.launch``).

The classifier's casts, pads and head and one kernel dispatch per layer, on
the host: the mean over the window's ticks of
``TickMetrics.phase_s["engine.launch"]``.  The device may still be running
the launch when it ends.
"""

from bench.engine_trace import phase_ms


def read(run):
    return phase_ms(run, "engine.launch")
